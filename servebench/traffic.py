"""The one general traffic generator: a traffic file in, a plan out.

A traffic file (`servebench/traffic/<name>.json`) fixes a MULTISET of
work, and `--seed` only permutes it:

* lengths are the n quantiles (at (i + 0.5) / n) of the file's prompt
  and output distributions, paired by a permutation that depends on the
  file alone, so the multiset of (prompt length, output length) pairs
  is the same under every seed;
* an open loop's inter-arrival gaps are the n quantiles of an
  exponential distribution (a Poisson process, stratified), scaled so
  that they sum to exactly the span they cover;
* the seed decides token ids and nothing else. The server takes requests
  in the order they come, so the order is part of the work: on the chip
  (PR 23) six seeds that only reordered one multiset spread `out_tok_s`
  by 4.6% of its median, while two runs of one seed agreed to 0.1%. So
  the order, too, is the file's own, and two seeds offer the same work.

Lead-in and window each get a multiset of their own, so the window holds
the same requests whatever the lead-in drew.

stdlib only: the harness's parent process never imports JAX.
"""
from __future__ import annotations

import json
import math
import random
import statistics
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

#: permutation that pairs prompt with output quantiles: a function of
#: the traffic file's name alone (never of --seed)
_PAIRING_SALT = "servebench-pairing"


@dataclass
class Request:
    """One planned request. `due` is seconds after the schedule's zero
    (open loop) or None (closed loop: due when the client's previous
    request finished)."""
    rid: str
    tokens: List[int]
    max_tokens: int
    due: float | None = None
    phase: str = "window"      # "lead" | "window"
    client: int | None = None


@dataclass
class Plan:
    kind: str                              # "burst" | "closed" | "open"
    lead_s: float                          # open loop: schedule zero to window open
    grace_s: float                         # open loop: wait for first tokens after close
    #: closed loop: one queue of requests per client, first-wave first
    queues: List[List[Request]] = field(default_factory=list)
    #: open loop: requests in due order
    schedule: List[Request] = field(default_factory=list)
    late_limit_ms: float = 50.0
    #: burst and closed loop: the window opens when this many requests
    #: have finished (0: when lead_s has passed), at the latest lead_max_s
    #: after the first request went out
    lead_finished: int = 0
    lead_max_s: float = 0.0


def quantile(dist: Dict, q: float) -> int:
    """Quantile q in (0, 1) of a length distribution, as a whole number
    inside [lo, hi]."""
    kind, lo, hi = dist["dist"], int(dist["lo"]), int(dist["hi"])
    if kind == "uniform":
        v = lo + q * (hi - lo)
    elif kind == "loguniform":
        v = lo * (hi / lo) ** q
    elif kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(q)
        v = float(dist["median"]) * math.exp(float(dist["sigma"]) * z)
    elif kind == "fixed":
        v = float(dist["value"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return max(lo, min(hi, int(round(v))))


def spread_order(n: int) -> List[int]:
    """0..n-1 in bit-reversed order: neighbours in this order lie far
    apart in rank, so any stretch of it holds a balanced mix."""
    bits = max(1, (n - 1).bit_length())
    return sorted(range(n),
                  key=lambda i: int(format(i, f"0{bits}b")[::-1], 2))


def length_pairs(traffic: Dict, n: int, salt: str) -> List[Tuple[int, int]]:
    """The multiset of n (prompt, output) pairs, in the file's own
    order: quantiles of both distributions; the outputs run in
    bit-reversed rank order (long and short answers alternate, so any
    stretch of the list asks for about the same work), and the prompts
    are paired with them by a permutation drawn from the file's name and
    `salt`, never from the seed."""
    qs = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(traffic["prompt"], q) for q in qs]
    outputs = [quantile(traffic["output"], qs[i]) for i in spread_order(n)]
    random.Random(f"{_PAIRING_SALT}:{traffic['name']}:{salt}:{n}").shuffle(prompts)
    return list(zip(prompts, outputs))


def gap_multiset(n: int, span_s: float) -> List[float]:
    """n inter-arrival gaps: quantiles of an exponential distribution,
    scaled to sum to span_s exactly (so the rate over the span is
    n / span_s under every order)."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return [g * scale for g in raw]


def _tokens(rng: random.Random, n: int, vocab: int) -> List[int]:
    return [rng.randrange(1, vocab) for _ in range(n)]


def load_traffic(path: Path) -> Dict:
    with open(path) as f:
        traffic = json.load(f)
    traffic.setdefault("name", Path(path).stem)
    return traffic


def make_plan(traffic: Dict, seed: int, seconds: float, vocab: int,
              max_seq: int) -> Plan:
    """The plan for one run. Everything random comes from `seed`; the
    multisets come from the traffic file and `seconds` alone."""
    rng = random.Random(int(seed))
    kind = traffic["kind"]

    plan = Plan(kind=kind, lead_s=float(traffic.get("lead_s", 0.0)),
                grace_s=float(traffic.get("ttft_grace_s", 0.0)),
                late_limit_ms=float(traffic.get("late_limit_ms", 50.0)),
                lead_finished=int(traffic.get("lead_until_finished", 0)),
                lead_max_s=float(traffic.get("lead_max_s",
                                             traffic.get("lead_s", 0.0))))

    def request(rid, p, o, **kw):
        if p + o > max_seq:
            raise ValueError(f"traffic {traffic['name']}: prompt {p} + "
                             f"output {o} exceeds max_seq {max_seq}")
        return Request(rid=f"s{seed}-{rid}", tokens=_tokens(rng, p, vocab),
                       max_tokens=o, **kw)

    if kind == "burst":
        # an offline batch: every request goes out at once, and the server
        # takes them in the order sent. Every round of `per_round`
        # requests is the same multiset of pairs, so any stretch of the
        # queue holds the same mix of lengths
        per_round, rounds = int(traffic["per_round"]), int(traffic["rounds"])
        for j in range(rounds):
            for i, (p, o) in enumerate(length_pairs(traffic, per_round,
                                                    "round")):
                plan.queues.append([request(f"b{j}-{i}", p, o,
                                            client=len(plan.queues))])
        return plan
    if kind == "closed":
        clients = int(traffic["clients"])
        rounds = int(traffic["rounds"])
        # first wave: the multiset's own pairs with the OUTPUT cut to a
        # fixed stagger between one token and the nominal length, so
        # that streams do not finish and re-admit in lock-step
        first = length_pairs(traffic, clients, "first")
        frac = [(i + 1) / clients for i in spread_order(clients)]
        for c in range(clients):
            p, o = first[c]
            plan.queues.append([request(
                f"c{c}-0", p, max(1, int(round(o * frac[c]))),
                phase="lead", client=c)])
        # every later round (the j-th request of each client) is the
        # same multiset of `clients` pairs: however many rounds a run gets
        # through, two seeds have offered the same work
        for j in range(1, rounds + 1):
            for c, (p, o) in enumerate(length_pairs(traffic, clients,
                                                    "round")):
                plan.queues[c].append(request(f"c{c}-{j}", p, o, client=c))
        return plan
    if kind != "open":
        raise ValueError(f"unknown traffic kind {kind!r}")
    rate = float(traffic["rate_rps"])
    k = 0
    t = 0.0
    for phase, span in (("lead", plan.lead_s), ("window", float(seconds))):
        n = max(1, int(round(rate * span)))
        # a Poisson process has runs of short gaps: the gaps keep an
        # order drawn from the file's name, not an evened-out one
        gaps = gap_multiset(n, span)
        random.Random(f"{_PAIRING_SALT}:{traffic['name']}:gaps:{phase}:{n}"
                      ).shuffle(gaps)
        pairs = length_pairs(traffic, n, phase)
        # arrival i closes gap i; the whole phase is moved up by half of
        # its first gap, so every arrival lies inside the phase and the
        # gaps between arrivals are the multiset's own
        at = t - gaps[0] / 2
        for g, (p, o) in zip(gaps, pairs):
            at += g
            plan.schedule.append(request(f"o{k}", p, o, due=at, phase=phase))
            k += 1
        t += span
    return plan
