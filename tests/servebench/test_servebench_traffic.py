"""The generator: the seed permutes a fixed multiset, it never redraws."""
import json
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench.manifest import load_manifest  # noqa: E402
from servebench.traffic import (gap_multiset, length_pairs, load_traffic,  # noqa: E402
                                make_plan, quantile, spread_order)

TRAFFIC = ROOT / "servebench" / "traffic"
SEEDS = (1, 2 ** 31 + 12345)   # the driver's seeds do not fit 32 signed bits


def plan(name, seed, seconds=45.0):
    return make_plan(load_traffic(TRAFFIC / f"{name}.json"), seed, seconds,
                     vocab=32768, max_seq=2048)


def pairs(reqs):
    return Counter((len(r.tokens), r.max_tokens) for r in reqs)


def test_burst_is_the_same_work_under_every_seed():
    a, b = (plan("batch", s) for s in SEEDS)
    ra = [q[0] for q in a.queues]
    rb = [q[0] for q in b.queues]
    assert len(ra) == len(rb) == 4096 and all(len(q) == 1 for q in a.queues)
    # the server takes requests in the order sent, so the order is part of
    # the work: the file fixes it, and the seed changes token ids alone
    assert [(len(r.tokens), r.max_tokens) for r in ra] == \
        [(len(r.tokens), r.max_tokens) for r in rb]
    assert all(x.tokens != y.tokens for x, y in zip(ra[:32], rb[:32]))
    # every round of 32 is the same multiset, and any stretch of eight
    # answers asks for about the same number of tokens
    rounds = [pairs(ra[i:i + 32]) for i in range(0, 4096, 32)]
    assert all(r == rounds[0] for r in rounds)
    outs = [r.max_tokens for r in ra]
    sums = [sum(outs[i:i + 8]) for i in range(0, 4088)]
    assert max(sums) / min(sums) < 1.08
    assert min(outs) >= 128 and max(outs) <= 256
    assert 185 <= sorted(outs[:32])[16] <= 199
    assert a.lead_finished == 16 and a.lead_max_s >= 60
    # the first 256 go out at once, as the whole batch did until PR 26:
    # what a server's default queue takes. Nothing more goes out while 64
    # of those sent wait, and the edges of the window wait for a burst of
    # tokens to end
    assert (a.keep_unstarted, a.refill_below) == (256, 64)
    assert a.edge_quiet_s == 0.05
    for c in load_manifest(ROOT)["configs"]:
        serve = json.loads((ROOT / c["file"]).read_text())["serve"]
        assert serve.get("max_queue", 256) >= a.keep_unstarted
    # the batch outlasts lead-in and window on a program nine times as
    # fast as four chips are today (1,553.7 tokens/s and about 25 s of
    # lead-in at the most, ledger, PR 31: then 9 x 1,553.7 for 25 / 9 s
    # and the 45 s window with the tenth of it that an edge may wait).
    # The 1,024 requests it held until PR 32 (196,608 tokens) lasted a
    # program up to two and a half times as fast and no further
    assert sum(outs) == 786_432 > 1.05 * 9 * 1553.7 * (25 / 9 + 49.5)
    assert sum(outs[:1024]) == 196_608 < 2.5 * 1553.7 * (25 / 2.5 + 49.5)


@pytest.mark.parametrize("seed", [1, 41, 2 ** 31 + 12345])
def test_the_first_1024_requests_are_the_parents_whole_batch(seed):
    """PR 32 made `rounds` 128 where it was 32, and nothing else that
    the generator reads: the rounds are equal multisets and the token
    ids come from one generator in the order sent, so what a server is
    sent before the window closes (449 requests on one chip, 642 on four,
    PERF.md) is what the parent's file sent it, to the byte."""
    traffic = load_traffic(TRAFFIC / "batch.json")
    assert (traffic["per_round"], traffic["rounds"]) == (32, 128)
    now = make_plan(traffic, seed, 45.0, 32768, 2048)
    was = make_plan(dict(traffic, rounds=32), seed, 45.0, 32768, 2048)
    assert len(was.queues) == 1024 and len(now.queues) == 4096
    assert now.queues[:1024] == was.queues
    first = [q[0] for q in now.queues[:1024]]
    assert [r.rid for r in first] == [
        f"s{seed}-b{j}-{i}" for j in range(32) for i in range(32)]
    assert (now.keep_unstarted, now.refill_below) == (256, 64)
    assert {f: getattr(now, f) for f in vars(now) if f != "queues"} == \
        {f: getattr(was, f) for f in vars(was) if f != "queues"}


def test_a_burst_file_without_a_depth_sends_its_whole_batch():
    t = {"name": "b", "kind": "burst", "per_round": 4, "rounds": 3,
         "prompt": {"dist": "fixed", "value": 8, "lo": 8, "hi": 8},
         "output": {"dist": "fixed", "value": 8, "lo": 8, "hi": 8}}
    p = make_plan(t, 1, 4.0, 100, 64)
    assert (p.keep_unstarted, p.refill_below, p.edge_quiet_s) == (12, 12, 0.0)
    p = make_plan(dict(t, keep_unstarted=6), 1, 4.0, 100, 64)
    assert (p.keep_unstarted, p.refill_below) == (6, 6)
    with pytest.raises(ValueError, match="refill_below"):
        make_plan(dict(t, keep_unstarted=6, refill_below=7), 1, 4.0, 100, 64)


def test_spread_order_is_a_permutation_that_alternates():
    for n in (1, 2, 7, 16, 32, 100):
        o = spread_order(n)
        assert sorted(o) == list(range(n))
    o = spread_order(32)
    assert all(abs(x - y) >= 8 for x, y in zip(o, o[1:]))


CLOSED = {"name": "loop", "kind": "closed", "clients": 8, "rounds": 3,
          "prompt": {"dist": "loguniform", "lo": 32, "hi": 128},
          "output": {"dist": "uniform", "lo": 128, "hi": 256}, "lead_s": 5}


def lengths(reqs):
    return [(len(r.tokens), r.max_tokens, r.due) for r in reqs]


def test_closed_loop_is_the_same_work_under_every_seed():
    a, b = (make_plan(CLOSED, s, 45.0, 32768, 2048) for s in SEEDS)
    assert len(a.queues) == len(b.queues) == 8
    for qa, qb in zip(a.queues, b.queues):
        assert lengths(qa) == lengths(qb) and len(qa) == 4
        assert qa[1].tokens != qb[1].tokens
    first = [q[0] for q in a.queues]
    assert all(r.phase == "lead" for r in first)
    assert len({r.max_tokens for r in first}) > 4          # staggered
    for j in (1, 2, 3):                                     # equal rounds
        assert pairs([q[j] for q in a.queues]) == pairs([q[1] for q in a.queues])


def test_open_loop_is_the_same_work_under_every_seed():
    a, b = (plan("chat", s) for s in SEEDS)
    assert lengths(a.schedule) == lengths(b.schedule)
    assert all(x.tokens != y.tokens for x, y in zip(a.schedule, b.schedule))
    for phase in ("lead", "window"):
        rs = [r for r in a.schedule if r.phase == phase]
        due = [r.due for r in rs]
        got = sorted(y - x for x, y in zip(due, due[1:]))
        span = 45.0 if phase == "window" else a.lead_s
        want = sorted(gap_multiset(len(rs), span))
        # every gap but the one the phase starts with is the multiset's own
        assert all(any(abs(g - w) < 1e-9 for w in want) for g in got)


def test_open_loop_window_holds_its_requests():
    traffic = load_traffic(TRAFFIC / "chat.json")
    for seed in SEEDS:
        p = make_plan(traffic, seed, 45.0, 32768, 2048)
        lead = traffic["lead_s"]
        win = [r for r in p.schedule if r.phase == "window"]
        assert len(win) == round(traffic["rate_rps"] * 45.0)
        assert all(lead <= r.due < lead + 45.0 for r in win)
        assert all(r.due < lead for r in p.schedule if r.phase == "lead")
        assert p.schedule == sorted(p.schedule, key=lambda r: r.due)


def test_gap_multiset_sums_to_its_span():
    g = gap_multiset(16, 45.0)
    assert sum(g) == pytest.approx(45.0)
    assert sorted(g) == g and g[0] > 0
    # exponential: the median gap is ln 2 of the mean
    assert g[8] / (45.0 / 16) == pytest.approx(0.72, abs=0.1)


@pytest.mark.parametrize("dist,lo,mid,hi", [
    ({"dist": "uniform", "lo": 128, "hi": 256}, 128, 192, 256),
    ({"dist": "loguniform", "lo": 32, "hi": 128}, 32, 64, 128),
    ({"dist": "lognormal", "median": 192, "sigma": 0.8, "lo": 32, "hi": 1024},
     32, 192, 1024),
])
def test_quantiles(dist, lo, mid, hi):
    assert quantile(dist, 0.5) == mid
    assert quantile(dist, 1e-9) == lo and quantile(dist, 1 - 1e-9) == hi
    qs = [quantile(dist, (i + 0.5) / 50) for i in range(50)]
    assert qs == sorted(qs)


def test_pairing_is_the_files_not_the_seeds():
    t = load_traffic(TRAFFIC / "batch.json")
    assert length_pairs(t, 32, "round") == length_pairs(t, 32, "round")
    assert length_pairs(t, 32, "round") != length_pairs(t, 32, "first")


def test_token_ids_in_vocabulary_and_from_seed():
    a, b = plan("batch", 5), plan("batch", 5)
    assert a.queues[3][0].tokens == b.queues[3][0].tokens
    assert all(1 <= t < 32768 for q in a.queues[:64] for r in q for t in r.tokens)


def test_prompt_plus_output_must_fit():
    t = dict(load_traffic(TRAFFIC / "batch.json"))
    with pytest.raises(ValueError):
        make_plan(t, 1, 45.0, 32768, max_seq=200)
