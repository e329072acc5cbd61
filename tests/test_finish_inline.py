"""A finish is taken at the lazy drain (ISSUE 40): without speculation
and without the seq-parallel lane a request that ends in the oldest block in flight
is finished THERE, with the newer block still on the device; no full
barrier runs, the freed slot and pages go to a waiter in the same tick,
and the block that tick dispatches chains on the newest one in flight.

The contract under test, hazard by hazard (tick()'s docstring has the
argument for each):

* (i) the finished request's lane is dead from the first step of every
  newer block, after a budget finish and after a stop-token finish: the
  tokens every request is served equal those it is served alone, one
  request at a time and ONE block in flight, where nothing newer than
  the block a finish surfaces in exists;
* (ii) the slot's next request starts with its OWN budget: a newer
  block's emission estimate for the slot's old request is not
  subtracted from it (positive after a stop-death);
* (iii) the old request's chain token, frozen at its stop id, does not
  start a new request with the SAME stop id dead, a warm-prefix
  admission whose cached prefix is its prompt less one token included;
* (iv) a page the finish released is granted to another request in the
  same tick and the old owner's staged rows land first; with prefix
  caching a page the finish registered is hit by the same tick's
  admission;
* (v) the write-combined window across such a finish, on and off;
* (vi) the page preallocation with a block in flight at admission;
* (vii) the same device edits on sharded carries (the CPU mesh);

and the counters: `drain_barriers_total{cause="finish"}` stays 0,
`finishes_inline_total` counts every finish a lazy drain took, the tick
record carries the tick's. The speculative path and a scheduler with
the seq-parallel lane keep their barrier.
"""
import jax
import pytest

from butterfly_tpu.core.config import MeshConfig, RuntimeConfig, tiny
from butterfly_tpu.core.mesh import make_mesh
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler

CFG = tiny("llama", dtype="float32", param_dtype="float32")
_PARAMS = None

#: three slots, blocks of four steps, two in flight, one chunk of 8 a
#: step: a finish surfaces at a lazy drain with one block in flight
BASE = dict(max_batch_size=3, max_seq_len=96, page_size=8,
            decode_steps_per_tick=4, inflight_blocks=2,
            prefill_chunk=8, prefill_inline_budget=8)


def params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = Model(CFG).init(jax.random.PRNGKey(42))
    return _PARAMS


def make_sched(mesh=None, **rt_kw):
    rt = RuntimeConfig(**{**BASE, **rt_kw})
    return Scheduler(ServingEngine(Model(CFG), params(), rt, mesh=mesh))


def alone(jobs, **rt_kw):
    """Every job served ALONE, one request at a time in the order given,
    one block in flight (nothing newer in flight at a finish), in one
    scheduler so that a prefix cache fills as it does in the run under
    test."""
    sched = make_sched(**{**rt_kw, "inflight_blocks": 1})
    outs = []
    for prompt, max_new, stop in jobs:
        r = sched.submit(list(prompt), max_new_tokens=max_new,
                         stop_token=stop)
        sched.run_until_done()
        assert r.state == "finished"
        outs.append(r.output)
    return outs


class Watch:
    """What the run under test did at each finish: the blocks in flight
    when a waiter was admitted, the budget each request was given in its
    first block, the finishes each kind of drain surfaced, the pages a
    finish released and who held them when the tick ended."""

    def __init__(self, sched):
        self.sched = sched
        self.first_budget = {}          # request id -> budget, first block
        self.inflight_at_admit = []     # len(_inflight) at each admission
        self.inflight_after_tick = []   # between first and last finish
        self.at_barriers = 0            # finishes a full barrier surfaced
        self.released = []              # pages released in the tick under way
        self.regranted = 0              # of them, held again at the tick's end
        eng, alloc = sched.engine, sched.alloc
        launch, seed = eng.mixed_block_async, sched._seed_mixed_slot
        barrier, release = sched._drain_inflight, alloc.release

        def mixed_block_async(tokens, cursor, pbuf, plen, active, temps,
                              stops, budgets, *rest):
            for slot, req in enumerate(sched.slots):
                if req is not None and req.id not in self.first_budget:
                    self.first_budget[req.id] = int(budgets[slot])
            return launch(tokens, cursor, pbuf, plen, active, temps,
                          stops, budgets, *rest)

        def _seed_mixed_slot(req):
            self.inflight_at_admit.append(len(sched._inflight))
            return seed(req)

        def _drain_inflight(cause="finish"):
            n = barrier(cause)
            self.at_barriers += n
            return n

        def released_pages(slot):
            self.released.extend(alloc.pages_of(slot))
            return release(slot)

        eng.mixed_block_async = mixed_block_async
        sched._seed_mixed_slot = _seed_mixed_slot
        sched._drain_inflight = _drain_inflight
        alloc.release = released_pages

    def run(self, jobs, lead=0):
        """Submit `jobs` (the first `lead` of them, then a tick, then the
        rest) and tick until done."""
        sched = self.sched
        reqs = []
        for i, (prompt, max_new, stop) in enumerate(jobs):
            if lead and i == lead:
                sched.tick()
            reqs.append(sched.submit(list(prompt), max_new_tokens=max_new,
                                     stop_token=stop))
        for _ in range(2000):
            if not sched.has_work:
                break
            self.released = []
            inline0 = sched._c_finish_inline.value
            sched.tick()
            if sched._c_finish_inline.value > inline0:
                rec = sched.ticklog.dump()["ticks"][-1]
                assert rec["finishes_inline"] == \
                    sched._c_finish_inline.value - inline0
                assert "finish" not in rec["barrier_causes"]
                if sched.has_work:
                    self.inflight_after_tick.append(len(sched._inflight))
                held = {p for s in range(sched.engine.num_slots)
                        for p in sched.alloc.pages_of(s)}
                self.regranted += len(held & set(self.released))
        assert not sched.has_work
        assert all(r.state == "finished" for r in reqs)
        return reqs


def _staggered(stop=-1, n=8):
    """`n` short prompts whose budgets differ, so that finishes come one
    at a time, each with a waiter behind it: prompts of 3-6 tokens,
    budgets 5, 8, 11, ... (never a multiple of the block's 4 steps twice
    in a row, so a finish falls on every step of a block)."""
    return [([3 + i, 7 + 2 * i, 11 + i, 5][:3 + i % 2] + [9 + i] * (i % 3),
             5 + 3 * (i % 5) + i // 5, stop) for i in range(n)]


def _check_counters(sched, watch, reqs):
    """No finish barrier ran; every finish a full barrier did not
    surface was taken at a lazy drain; a block was in flight through
    every one of them and at every admission behind one."""
    assert sched.barrier_causes().get("finish", 0) == 0
    m = sched.metrics()
    assert m["finishes_inline_total"] == len(reqs) - watch.at_barriers
    assert m["finishes_inline_total"] >= len(reqs) - 2   # the run's tail
    assert m["requests_finished"] == len(reqs)
    # _inflight was never empty between the first and the last finish
    assert watch.inflight_after_tick and min(watch.inflight_after_tick) >= 1
    # the waiters (all but the first three) were admitted behind a block
    assert len(watch.inflight_at_admit) == len(reqs)
    assert min(watch.inflight_at_admit[3:]) >= 1
    # (ii) every request started with its own budget
    assert watch.first_budget == {r.id: r.max_new_tokens for r in reqs}


GRID = {
    "float": dict(),
    "int8kv": dict(kv_quant="int8"),
    "nowindow": dict(kv_write_combine=False),
    "nowindow-int8kv": dict(kv_write_combine=False, kv_quant="int8"),
    "depth3": dict(inflight_blocks=3),
}


@pytest.mark.parametrize("rt_kw", list(GRID.values()), ids=list(GRID))
def test_budget_finish_at_the_lazy_drain(rt_kw):
    """(i), (v), (vi): staggered budgets, eight requests through three
    slots; each finish surfaces at a lazy drain with a block in flight
    and the waiter behind it is admitted in the same tick."""
    jobs = _staggered()
    sched = make_sched(**rt_kw)
    watch = Watch(sched)
    reqs = watch.run(jobs)
    assert [r.output for r in reqs] == alone(jobs, **rt_kw)
    _check_counters(sched, watch, reqs)
    assert sched.metrics()["preemptions_total"] == 0


def _stop_jobs(rt_kw, n=8):
    """The staggered jobs with generous budgets and ONE stop id for all:
    the token that most of their answers hold, so most requests die
    wherever their own answer meets it, with budget to spare, and the
    rest by budget."""
    free = [(p, 24, -1) for p, _, _ in _staggered(n=n)]
    outs = alone(free, **rt_kw)
    held = {}
    for out in outs:
        for t in set(out):
            held[t] = held.get(t, 0) + 1
    stop = max(held, key=lambda t: (held[t], -t))
    jobs = [(p, 24 - 2 * (i % 4), stop) for i, (p, _, _) in enumerate(free)]
    want = []
    for (p, max_new, _), out in zip(jobs, outs):
        out = out[:max_new]
        want.append(out[:out.index(stop) + 1] if stop in out else out)
    return jobs, want, stop


@pytest.mark.parametrize("rt_kw", [GRID["float"], GRID["int8kv"],
                                   GRID["nowindow"]],
                         ids=["float", "int8kv", "nowindow"])
def test_stop_token_finish_at_the_lazy_drain(rt_kw):
    """(i), (ii), (iii): requests that die at their stop token with
    budget to spare, every request carrying the SAME stop id, so each
    slot's next request finds its slot's chain token frozen at its own
    stop id and a newer block's estimate made for the dead request."""
    jobs, want, stop = _stop_jobs(rt_kw)
    assert sum(w[-1] == stop for w in want) >= 3    # stop-deaths happen
    assert any(len(w) < j[1] - 4 for w, j in zip(want, jobs))
    assert alone(jobs, **rt_kw) == want             # the reference agrees
    sched = make_sched(**rt_kw)
    watch = Watch(sched)
    reqs = watch.run(jobs)
    assert [r.output for r in reqs] == want
    _check_counters(sched, watch, reqs)


def test_estimate_of_a_dead_request_is_not_the_next_request_s():
    """(ii) at the place it bites: a block in flight was given budget
    for a request that then died at its stop token; its estimate for
    the slot is positive, and the slot's next request, admitted behind
    that block, is still handed its whole budget."""
    jobs, want, stop = _stop_jobs({}, n=4)
    sched = make_sched(max_batch_size=1)
    seen = []
    launch = sched.engine.mixed_block_async

    def spy(tokens, cursor, pbuf, plen, active, temps, stops, budgets,
            *rest):
        stale = [int(e[7][0]) for e in sched._inflight
                 if e[4][0][0] is not sched.slots[0]]
        seen.append((sched.slots[0].id, int(budgets[0]), stale))
        return launch(tokens, cursor, pbuf, plen, active, temps, stops,
                      budgets, *rest)
    sched.engine.mixed_block_async = spy
    reqs = [sched.submit(list(p), max_new_tokens=n, stop_token=s)
            for p, n, s in jobs]
    sched.run_until_done()
    assert [r.output for r in reqs] == want
    # some request's first block was dispatched behind a block whose
    # estimate for the slot's dead request was positive
    firsts = {}
    for rid, budget, stale in seen:
        firsts.setdefault(rid, (budget, stale))
    assert any(any(e > 0 for e in stale) for _, stale in firsts.values())
    for r in reqs:
        assert firsts[r.id][0] == r.max_new_tokens


@pytest.mark.parametrize("stop_finish", [False, True],
                         ids=["budget", "stop"])
@pytest.mark.parametrize("rt_kw", [dict(), dict(kv_quant="int8"),
                                   dict(kv_write_combine=False)],
                         ids=["float", "int8kv", "nowindow"])
def test_prefix_registered_at_the_finish_is_hit_in_the_same_tick(
        rt_kw, stop_finish):
    """(iii), (iv) with --prefix-caching: the waiter's prompt is the
    finished request's prompt AND answer (a second turn), so the pages
    the finish registers are the ones its admission hits, in the same
    tick, behind the block in flight and the pending flush; the prompt
    is cut so that the cached prefix is the whole prompt less one
    token. The finished request ends by budget or at its stop token;
    the second turn carries the same stop id."""
    rt_kw = dict(rt_kw, prefix_caching=True)
    ps = BASE["page_size"]
    turn1 = list(range(20, 31))                       # 11 tokens
    (full,) = alone([(turn1, 24, -1)], **rt_kw)
    stop = -1
    if stop_finish:
        stop = next(t for i, t in enumerate(full) if i >= 8
                    and t not in full[:i])
    out1 = full[:full.index(stop) + 1] if stop_finish else full[:14]
    n1 = 24 if stop_finish else len(out1)
    # the second turn: prompt + answer cut to a whole number of pages
    # that reaches into the ANSWER, plus one token
    said = turn1 + out1
    pages = (len(said) - 1) // ps
    assert pages * ps > len(turn1)
    turn2 = said[:pages * ps + 1]
    others = [([5, 7, 11], 40, -1), ([3, 1, 4, 1], 37, -1)]
    jobs = [(turn1, n1, stop)] + others + [(turn2, 9, stop)]
    want = alone(jobs, **rt_kw)
    assert want[0] == out1

    sched = make_sched(**rt_kw)
    watch = Watch(sched)
    hits = []
    admit = sched.alloc.admit

    def admit_spy(slot, tokens, need_len):
        cached = admit(slot, tokens, need_len)
        hits.append((len(tokens), cached, len(sched._inflight)))
        return cached
    sched.alloc.admit = admit_spy
    reqs = watch.run(jobs)
    assert [r.output for r in reqs] == want
    assert sched.barrier_causes().get("finish", 0) == 0
    assert sched.metrics()["finishes_inline_total"] >= 2
    # the second turn hit every page but its last token's, behind a block
    assert hits[-1] == (len(turn2), len(turn2) - 1, 1)
    assert watch.first_budget[reqs[-1].id] == 9


@pytest.mark.parametrize("rt_kw", [dict(), dict(kv_quant="int8"),
                                   dict(kv_write_combine=False)],
                         ids=["float", "int8kv", "nowindow"])
def test_page_released_at_the_finish_is_granted_in_the_same_tick(rt_kw):
    """(iv), (vi) in a tight pool: pages of 4 and a pool that holds the
    three running requests and little more, so the pages a finish
    releases are what the waiter's admission and the runners' growth
    are given in the same tick, with the old owner's block in flight
    and its staged rows in the pending flush."""
    rt_kw = dict(rt_kw, page_size=4, num_pages=22)
    jobs = _staggered(n=9)
    sched = make_sched(**rt_kw)
    watch = Watch(sched)
    reqs = watch.run(jobs)
    assert [r.output for r in reqs] == alone(jobs, **rt_kw)
    assert watch.regranted >= 3      # released and held again, same tick
    # and no barrier stood between the release and the grant
    assert set(sched.barrier_causes()) <= {"idle"}
    assert min(watch.inflight_at_admit[3:]) >= 1
    assert sched.metrics()["finishes_inline_total"] >= len(reqs) - 3
    assert sched.metrics()["preemptions_total"] == 0


def test_finish_at_the_lazy_drain_under_the_mesh():
    """(vii): the same finishes, admissions and device edits on carries
    sharded over the CPU mesh of tests/test_serving_mesh.py."""
    jobs = _staggered(n=6)
    mesh = make_mesh(MeshConfig(data=2, tensor=4))
    sched = make_sched(mesh=mesh)
    watch = Watch(sched)
    reqs = watch.run(jobs)
    assert [r.output for r in reqs] == alone(jobs)
    assert sched.barrier_causes().get("finish", 0) == 0
    assert sched.metrics()["finishes_inline_total"] \
        == len(reqs) - watch.at_barriers >= len(reqs) - 2
    assert min(watch.inflight_at_admit[3:]) >= 1
    assert watch.first_budget == {r.id: r.max_new_tokens for r in reqs}
    lengths = sched.engine.cache.lengths
    assert len(lengths.sharding.device_set) > 1


@pytest.mark.parametrize("rt_kw,seq,inline", [
    (dict(), 0, True),
    (dict(speculative_gamma=3), 0, False),
    (dict(seq_parallel_threshold=64), 2, False),
], ids=["mixed", "mixed-spec", "seq-lane"])
def test_which_paths_take_a_finish_without_a_barrier(rt_kw, seq, inline):
    """SEPARATE by mode: the plain mixed block takes a
    finish at the lazy drain; the speculative
    path and a scheduler whose mesh gives it the seq-parallel lane (its
    prompts here are all under the lane's threshold) keep the full
    barrier, and count nothing under finishes_inline_total."""
    jobs = _staggered(n=5)
    mesh = make_mesh(MeshConfig(seq=seq), jax.devices()[:seq]) if seq \
        else None
    sched = make_sched(mesh=mesh, **rt_kw)
    assert sched._sp_enabled is bool(seq)
    assert sched._finish_inline is inline
    reqs = [sched.submit(list(p), max_new_tokens=n) for p, n, _ in jobs]
    sched.run_until_done()
    assert [r.output for r in reqs] == alone(jobs)
    m, causes = sched.metrics(), sched.barrier_causes()
    ticks = sched.ticklog.dump()["ticks"]
    assert sum(t["finishes_inline"] for t in ticks) \
        == m["finishes_inline_total"]
    if inline:
        assert causes.get("finish", 0) == 0
        assert m["finishes_inline_total"] >= 3
    else:
        assert m["finishes_inline_total"] == 0
        assert causes.get("finish", 0) >= 1
