"""Metric arithmetic on synthetic timelines."""
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import metrics as M  # noqa: E402
from servebench.metrics import Stream  # noqa: E402


def stream(times, due=None, sent=None, asked=1000, prompt=10, **kw):
    return Stream(rid="r", prompt_len=prompt, asked=asked, due=due,
                  sent=sent if sent is not None else due, times=list(times), **kw)


def every(start, step, n):
    return [start + i * step for i in range(n)]


def test_window_edges_cut_throughput():
    s = stream(every(9.0, 0.1, 40))          # 9.0 .. 12.9
    rate, n = M.out_tok_s([s], 10.0, 12.0)
    assert n == 20 and rate == pytest.approx(10.0)
    assert M.out_tok_s([s], 20.0, 30.0) == (0.0, 0)


def test_tpot_needs_sixteen_tokens_inside():
    short = stream(every(10.0, 0.1, 15))
    enough = stream(every(10.0, 0.2, 16))
    v, n = M.tpot_p50_ms([short, enough], 10.0, 20.0)
    assert n == 1 and v == pytest.approx(200.0)
    assert M.tpot_p50_ms([short], 10.0, 20.0) == (None, 0)


def test_straddling_stream_gives_the_part_inside():
    s = stream(every(0.0, 0.25, 100))         # 0 .. 24.75, unfinished or not
    v, n = M.tpot_p50_ms([s], 10.0, 20.0)
    assert n == 1 and v == pytest.approx(250.0)
    assert not s.finished


def test_tpot_is_the_median_over_streams():
    ss = [stream(every(0.0, step, 100)) for step in (0.1, 0.2, 0.4)]
    assert M.tpot_p50_ms(ss, 0.0, 100.0)[0] == pytest.approx(200.0)


def bursts(start, period, n, size=4):
    """n deliveries of `size` tokens, `period` apart, a burst's tokens
    0.1 ms apart."""
    return [start + i * period + j * 1e-4 for i in range(n) for j in range(size)]


@pytest.mark.parametrize("timeline, want_ms", [
    # bursts of 4 every second read a quarter of a second exactly, where
    # tokens over (tokens - 1) would read 9 s / 39 = 231 ms
    (bursts(10.0, 1.0, 10), 250.0),
    # a finish barrier hands out two blocks at once: 8 tokens after two
    # periods. 9 s still carry 36 tokens after the first delivery
    (bursts(10.0, 1.0, 5) + bursts(16.0, 1.0, 1, size=8)
     + bursts(17.0, 1.0, 3), 250.0),
    # one token at a time, the old and the new reading are the same
    (every(10.0, 0.2, 16), 200.0),
    # the window's edge cuts a burst in two: the part inside is the
    # first delivery, and counts in neither span nor tokens
    ([9.9999, 10.0, 10.0001, 10.0002] + bursts(11.0, 1.0, 4), 250.0),
], ids=["bursts-of-4", "double-burst", "single-tokens", "cut-burst"])
def test_tpot_times_deliveries_not_tokens(timeline, want_ms):
    v, n = M.tpot_p50_ms([stream(timeline)], 10.0, 30.0)
    assert n == 1 and v == pytest.approx(want_ms)


def test_tpot_leaves_out_a_stream_with_one_delivery():
    one = stream(bursts(10.0, 1.0, 1, size=32))       # 32 tokens at once
    few = stream(bursts(10.0, 1.0, 3))                # 12 tokens inside
    two = stream(bursts(10.0, 2.0, 2, size=8))        # 16 tokens, 2 deliveries
    assert M.tpot_p50_ms([one, few], 10.0, 30.0) == (None, 0)
    v, n = M.tpot_p50_ms([one, few, two], 10.0, 30.0)
    assert n == 1 and v == pytest.approx(250.0)       # 2 s for 8 tokens


def test_deliveries_under_a_millisecond_are_one():
    t = [1.0, 1.0002, 1.0004, 1.0006, 1.2, 1.2003, 1.4]
    assert M.deliveries(t) == [1.0, 1.2, 1.4]
    gaps = M.delivery_gaps([stream(t)], 0.0, 2.0)
    assert gaps == pytest.approx([0.2, 0.2])
    # a chain of sub-millisecond arrivals stays one delivery
    assert M.deliveries([0.0, 0.0009, 0.0018, 0.0027]) == [0.0]


def test_gap_percentile_pools_streams_and_respects_the_window():
    a = stream(every(0.0, 0.1, 101))          # 100 gaps of 0.1
    b = stream([5.0, 6.0])                    # one gap of 1.0
    v, n = M.END_TO_END["gap_p95_ms"]([a, b], 0.0, 20.0)
    assert n == 101 and v == pytest.approx(100.0)
    v99, _ = M.END_TO_END["gap_p99_ms"]([a, b], 0.0, 20.0)
    assert v99 == pytest.approx(100.0)
    assert M.percentile([1, 2, 3, 4, 5], 50) == 3
    assert M.percentile([0, 10], 95) == pytest.approx(9.5)
    # a gap that ends outside the window is not counted
    assert M.delivery_gaps([b], 0.0, 5.5) == []


def test_ttft_counts_from_due_not_from_send():
    s = stream([12.0], due=10.0, sent=10.5)
    v, n = M.ttft_p50_ms([s], 0.0, 20.0)
    assert n == 1 and v == pytest.approx(2000.0)


def test_ttft_takes_requests_due_inside_the_window_only():
    inside = stream([11.0], due=10.5)
    before = stream([9.0], due=8.0)
    closed = stream([11.0], due=None)         # closed loop: no due time
    v, n = M.ttft_p50_ms([inside, before, closed], 10.0, 20.0)
    assert n == 1 and v == pytest.approx(500.0)


def test_failed_request_is_slower_than_any_other():
    ok = [stream([10.0 + d + 0.1 * i], due=10.0 + d)
          for i, d in enumerate((0.0, 1.0, 2.0))]
    refused = stream([], due=13.0, failed="refused: 429")
    v, n = M.ttft_p50_ms(ok + [refused], 0.0, 20.0)
    assert n == 4
    assert v == pytest.approx(150.0)          # between the 2nd and 3rd of 4
    two_bad = ok[:1] + [refused, stream([], due=14.0, failed="x")]
    assert math.isinf(M.ttft_p50_ms(two_bad, 0.0, 20.0)[0])


def test_thirds_split_the_window():
    s = stream(every(0.0, 0.1, 300))
    th = M.thirds([s], 0.0, 30.0, ["out_tok_s", "tpot_p50_ms", "setup_s"])
    assert th["out_tok_s"] == pytest.approx([10.0, 10.0, 10.0])
    assert th["tpot_p50_ms"] == pytest.approx([100.0] * 3)
    assert "setup_s" not in th


def test_live_contexts_count_prompt_and_tokens_so_far_a_stream_at_a_time():
    a = stream(every(1.0, 1.0, 10), prompt=100)         # live from 1.0 on
    b = stream([2.0, 3.0], prompt=50, finished=True, end=3.0)
    assert M.live_contexts([a, b], 2.5) == [100 + 2, 50 + 1]
    assert M.live_contexts([a, b], 3.5) == [103]
    assert M.live_contexts([a, b], 0.5) == []
