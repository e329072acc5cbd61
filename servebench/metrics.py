"""End-to-end metric arithmetic over client-side timelines.

Every number here is taken from timestamps of the harness's own clock
(`time.monotonic()` at the moment a `recv` returned the bytes). Only
tokens and events whose timestamps fall inside the window [w0, w1)
count; a stream that straddles an edge contributes the part inside.

stdlib only.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

#: a stream needs this many tokens inside the window to give a TPOT
MIN_TOKENS = 16
#: tokens that arrive closer together than this are one delivery
DELIVERY_MERGE_S = 0.001


@dataclass
class Stream:
    """What the client saw of one request."""
    rid: str
    prompt_len: int
    asked: int                      # max_tokens
    phase: str = "window"
    due: Optional[float] = None     # when the schedule wanted it sent
    sent: Optional[float] = None    # when it was sent
    times: List[float] = field(default_factory=list)   # one per token
    tokens: List[int] = field(default_factory=list)
    finished: bool = False          # saw [DONE]
    failed: Optional[str] = None    # refusal, error event, broken stream
    end: Optional[float] = None     # time of [DONE] or of the failure
    retries: int = 0                # 503s with Retry-After, sent again


def percentile(values: Sequence[float], q: float) -> float:
    """Percentile q in [0, 100] by linear interpolation between the
    closest ranks (numpy's default), over all of `values`."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    pos = q / 100.0 * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    if math.isinf(s[hi]) or math.isinf(s[lo]):
        return s[hi] if pos > lo else s[lo]
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def inside(times: Sequence[float], w0: float, w1: float) -> List[float]:
    return [t for t in times if w0 <= t < w1]


def out_tok_s(streams: Sequence[Stream], w0: float, w1: float):
    n = sum(len(inside(s.times, w0, w1)) for s in streams)
    return n / (w1 - w0), n


def stream_tpots(streams: Sequence[Stream], w0: float, w1: float) -> List[float]:
    """Per stream, over the DELIVERIES inside the window: (last delivery
    - first delivery) over the tokens delivered after the first
    delivery, for streams with at least MIN_TOKENS tokens and two
    deliveries inside, finished or not. Seconds per token.

    A server hands tokens out in bursts (a block of k decode steps, two
    blocks at a barrier). The first delivery's tokens were made before
    the span began, so they count in neither part: bursts of 4 every T
    read T / 4 exactly, and a double burst of 8 leaves the reading where
    it was, where tokens over (tokens - 1) read 3% under the mean gap
    (PR 25, on the chip)."""
    out = []
    for s in streams:
        ts = inside(s.times, w0, w1)
        d = deliveries(ts)
        if len(ts) >= MIN_TOKENS and len(d) >= 2:
            after = sum(1 for t in ts if t >= d[1])
            out.append((d[-1] - d[0]) / after)
    return out


def tpot_p50_ms(streams, w0, w1):
    v = stream_tpots(streams, w0, w1)
    return (statistics.median(v) * 1e3 if v else None), len(v)


def deliveries(times: Sequence[float]) -> List[float]:
    """Times of deliveries: tokens less than DELIVERY_MERGE_S after the
    previous token belong to the same delivery."""
    out: List[float] = []
    last = None
    for t in times:
        if last is None or t - last >= DELIVERY_MERGE_S:
            out.append(t)
        last = t
    return out


def delivery_gaps(streams, w0, w1) -> List[float]:
    """Gaps between successive deliveries to one client, both inside
    the window, pooled over all streams. Seconds."""
    out = []
    for s in streams:
        d = inside(deliveries(s.times), w0, w1)
        out.extend(b - a for a, b in zip(d, d[1:]))
    return out


def gap_pct_ms(q: float) -> Callable:
    def fn(streams, w0, w1):
        g = delivery_gaps(streams, w0, w1)
        return (percentile(g, q) * 1e3 if g else None), len(g)
    return fn


def ttfts(streams, w0, w1) -> List[float]:
    """From the moment a request was DUE to its first token, for the
    requests due inside the window; a request that was refused, failed
    or never got a first token counts as slower than any other."""
    out = []
    for s in streams:
        if s.due is None or not (w0 <= s.due < w1):
            continue
        out.append(s.times[0] - s.due if s.times else math.inf)
    return out


def ttft_p50_ms(streams, w0, w1):
    v = ttfts(streams, w0, w1)
    if not v:
        return None, 0
    m = percentile(v, 50)
    return (m * 1e3 if not math.isinf(m) else math.inf), len(v)


#: end-to-end metric name -> function(streams, w0, w1) -> (value, samples).
#: `setup_s` is the harness's own (run.py). A metric named
#: gap_p<q>_ms is the q-th percentile of the pooled delivery gaps.
END_TO_END: Dict[str, Callable] = {
    "out_tok_s": out_tok_s,
    "tpot_p50_ms": tpot_p50_ms,
    "ttft_p50_ms": ttft_p50_ms,
    "gap_p90_ms": gap_pct_ms(90),
    "gap_p95_ms": gap_pct_ms(95),
    "gap_p99_ms": gap_pct_ms(99),
}


def thirds(streams, w0, w1, names) -> Dict[str, List]:
    """Each metric over each third of the window: within-run spread, to
    set beside the spread between runs."""
    cut = [w0 + (w1 - w0) * i / 3 for i in range(4)]
    return {n: [END_TO_END[n](streams, cut[i], cut[i + 1])[0]
                for i in range(3)] for n in names if n in END_TO_END}


def live_contexts(streams, t: float) -> List[int]:
    """Tokens of context of each stream generating at time t: prompt plus
    the tokens it had received by t."""
    return [s.prompt_len + sum(1 for x in s.times if x <= t)
            for s in streams
            if s.times and s.times[0] <= t and (s.end is None or t < s.end)]
