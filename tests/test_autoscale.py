"""Elastic fleet: the closed-loop autoscaler (fleet/autoscale.py) and
the runtime spawn/retire path it drives (fleet/harness.py).

Two layers, matching the two-tier suite:

* Control-loop units on a FAKE pool: a real ReplicaPool object whose
  scrape rings are hand-fed and whose spawn/retire are counters — every
  decision branch (band, bounds, shed floor, cooldowns, victim choice,
  replica-seconds integral) is pinned with injectable time. These are
  the tests that must kill the mutcheck mutant inverting the
  scale-down hysteresis guard.
* Live in-process fleets (slow-marked in conftest.py): spawn joins and
  serves, retire drains without dropping a request, and the full
  closed loop reshapes a real topology both directions.
"""
import json
import threading
import urllib.request

import pytest

from butterfly_tpu.fleet.autoscale import Autoscaler, TierPolicy
from butterfly_tpu.obs.registry import MetricsRegistry
from butterfly_tpu.obs.ticklog import FlightRecorder
from butterfly_tpu.router.pool import ReplicaPool


# ---------------------------------------------------------------------------
# control-loop units (fake pool, injectable time)
# ---------------------------------------------------------------------------

class FakeState:
    """The slice of ControlPlaneState the autoscaler consumes."""

    def __init__(self, pool):
        self.pool = pool
        self.registry = MetricsRegistry()
        self.flightrec = FlightRecorder()


def make_pool(roles):
    """Pool of fake members (never started — no probes, no HTTP), one
    per role, ports counting up from 9001."""
    specs = [f"127.0.0.1:{9001 + i}" for i in range(len(roles))]
    pool = ReplicaPool(specs, probe_interval=999.0)
    for spec, role in zip(specs, roles):
        pool.replicas[spec].role = role
    return pool


def feed(pool, rid, signal, values):
    """Append fake scrape-ring samples for one replica."""
    for i, v in enumerate(values):
        pool.replicas[rid].series.append(
            {"t_wall": float(i), "signals": {signal: float(v)}})


class Fleet:
    """Fake spawn/retire: mutates pool membership and records calls."""

    def __init__(self, pool):
        self.pool = pool
        self.spawned = []
        self.retired = []
        self._next_port = 9500

    def spawn(self, role):
        rid = f"127.0.0.1:{self._next_port}"
        self._next_port += 1
        self.pool.add(rid)
        self.pool.replicas[rid].role = role
        self.spawned.append((role, rid))
        return rid

    def retire(self, rid):
        self.pool.remove(rid)
        self.retired.append(rid)
        return True


def make_scaler(roles, policies, **kw):
    pool = make_pool(roles)
    state = FakeState(pool)
    fleet = Fleet(pool)
    a = Autoscaler(state, fleet.spawn, fleet.retire, policies, **kw)
    return a, pool, fleet, state


def decision(step_out, role):
    (d,) = [d for d in step_out if d.tier == role]
    return d


def test_policy_validation():
    with pytest.raises(ValueError):
        TierPolicy("decode", min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        TierPolicy("decode", high=1.0, low=2.0)  # inverted band
    with pytest.raises(ValueError):
        Autoscaler(FakeState(make_pool(["decode"])), None, None,
                   [TierPolicy("decode"), TierPolicy("decode")])


def test_scale_up_on_sustained_high_signal():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                     high=4.0, low=0.5, window=3, cooldown_up_s=0.0)
    a, pool, fleet, state = make_scaler(["decode"], [pol])
    feed(pool, "127.0.0.1:9001", "queue_depth", [6, 7, 8])
    d = decision(a.step(now=100.0), "decode")
    assert d.direction == "up" and d.reason == "signal_high"
    assert fleet.spawned == [("decode", d.rid)]
    assert len(pool.replicas) == 2
    # the decision is in the flight recorder with its evidence
    events = state.flightrec.dump().get("events", [])
    scales = [e for e in events if e.get("kind") == "scale"]
    assert scales and scales[-1]["tier"] == "decode"
    assert scales[-1]["direction"] == "up"
    assert scales[-1]["reason"] == "signal_high"


def test_in_band_signal_holds():
    pol = TierPolicy("decode", high=4.0, low=0.5, window=3)
    a, pool, fleet, _ = make_scaler(["decode", "decode"], [pol])
    for rid in list(pool.replicas):
        feed(pool, rid, "queue_depth", [1, 2, 2])
    d = decision(a.step(now=100.0), "decode")
    assert d.direction is None and d.reason == "in_band"
    assert not fleet.spawned and not fleet.retired


def test_no_ring_data_holds():
    pol = TierPolicy("decode", high=4.0, low=0.5)
    a, _, fleet, _ = make_scaler(["decode"], [pol])
    d = decision(a.step(now=100.0), "decode")
    assert d.direction is None and d.reason == "no_data"
    assert not fleet.spawned


def test_scale_down_hysteresis_cooldown():
    """The mutcheck anchor: a shrink is refused until a FULL
    cooldown_down_s has passed since the tier's last scale action, and
    allowed after. Both branches asserted, so inverting the guard
    (acting inside the window, holding outside it) fails either way."""
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                     high=4.0, low=0.5, window=2,
                     cooldown_up_s=0.0, cooldown_down_s=10.0)
    a, pool, fleet, _ = make_scaler(["decode"], [pol])
    feed(pool, "127.0.0.1:9001", "queue_depth", [9, 9])
    assert decision(a.step(now=100.0), "decode").direction == "up"

    # tier goes idle immediately after the grow
    for rid in list(pool.replicas):
        pool.replicas[rid].series.clear()
        feed(pool, rid, "queue_depth", [0, 0])

    # inside the window: wanted down, must HOLD
    d = decision(a.step(now=104.0), "decode")
    assert d.direction is None and d.reason == "cooldown_down"
    assert not fleet.retired and len(pool.replicas) == 2

    # outside the window: the shrink goes through
    d = decision(a.step(now=111.0), "decode")
    assert d.direction == "down" and d.reason == "signal_low"
    assert len(fleet.retired) == 1 and len(pool.replicas) == 1


def test_scale_up_cooldown_rate_limits_growth():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=4,
                     high=4.0, low=0.5, window=2, cooldown_up_s=5.0)
    a, pool, fleet, _ = make_scaler(["decode"], [pol])
    feed(pool, "127.0.0.1:9001", "queue_depth", [9, 9])
    assert decision(a.step(now=100.0), "decode").direction == "up"
    # still saturated 1s later: held, not a spawn storm
    d = decision(a.step(now=101.0), "decode")
    assert d.direction is None and d.reason == "cooldown_up"
    assert len(fleet.spawned) == 1
    assert decision(a.step(now=106.0), "decode").direction == "up"


def test_bounds_cap_and_floor():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=2,
                     high=4.0, low=0.5, window=2, cooldown_up_s=0.0,
                     cooldown_down_s=0.0)
    a, pool, fleet, _ = make_scaler(["decode", "decode"], [pol])
    for rid in list(pool.replicas):
        feed(pool, rid, "queue_depth", [9, 9])
    d = decision(a.step(now=100.0), "decode")
    assert d.direction is None and d.reason == "at_max"

    for rid in list(pool.replicas):
        pool.replicas[rid].series.clear()
        feed(pool, rid, "queue_depth", [0, 0])
    assert decision(a.step(now=101.0), "decode").direction == "down"
    # now at min: idle no longer shrinks
    d = decision(a.step(now=102.0), "decode")
    assert d.direction is None and d.reason == "at_min"
    assert len(pool.replicas) == 1


def test_below_min_spawns_ignoring_cooldown():
    """min_replicas is a bound, not a suggestion: an empty tier (the
    '0p4d' elastic starting shape, or after a crash) refills even
    inside the up-cooldown."""
    pol = TierPolicy("prefill", min_replicas=1, max_replicas=2,
                     cooldown_up_s=1e9)
    a, pool, fleet, _ = make_scaler(["decode"], [pol])
    d = decision(a.step(now=100.0), "prefill")
    assert d.direction == "up" and d.reason == "below_min"
    assert fleet.spawned[0][0] == "prefill"


def test_shed_floor_forces_scale_up():
    """PR 8's admission shedding is the backpressure floor: a tier
    whose replicas return 429s scales up even with the gauge in band."""
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                     high=4.0, low=0.5, window=2, cooldown_up_s=0.0)
    a, pool, fleet, _ = make_scaler(["decode"], [pol])
    rid = "127.0.0.1:9001"
    feed(pool, rid, "queue_depth", [1, 1])  # in band

    def shed_families(total):
        return {"butterfly_shed_total": {
            "type": "counter",
            "samples": {("butterfly_shed_total",
                         (("priority", "batch"),)): float(total)}}}

    pool.replicas[rid].metrics_families = shed_families(5)
    # first sight of the counter only establishes the baseline
    d = decision(a.step(now=100.0), "decode")
    assert d.direction is None and d.reason == "in_band"

    pool.replicas[rid].metrics_families = shed_families(9)  # 4 new sheds
    d = decision(a.step(now=101.0), "decode")
    assert d.direction == "up" and d.reason == "shed_floor"
    assert len(fleet.spawned) == 1


def test_tiers_scale_independently_same_step():
    pols = [TierPolicy("prefill", min_replicas=1, max_replicas=3,
                       high=4.0, low=0.5, window=2, cooldown_up_s=0.0),
            TierPolicy("decode", min_replicas=1, max_replicas=3,
                       high=4.0, low=0.5, window=2, cooldown_down_s=0.0)]
    a, pool, fleet, _ = make_scaler(["prefill", "decode", "decode"], pols)
    feed(pool, "127.0.0.1:9001", "queue_depth", [9, 9])     # prefill hot
    feed(pool, "127.0.0.1:9002", "queue_depth", [0, 0])     # decode idle
    feed(pool, "127.0.0.1:9003", "queue_depth", [0, 0])
    out = a.step(now=100.0)
    assert decision(out, "prefill").direction == "up"
    assert decision(out, "decode").direction == "down"
    assert fleet.spawned[0][0] == "prefill"
    roles = [r.role for r in pool.replicas.values()]
    assert roles.count("prefill") == 2 and roles.count("decode") == 1


def test_retire_victim_is_least_loaded():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                     high=4.0, low=1.0, window=2, cooldown_down_s=0.0)
    a, pool, fleet, _ = make_scaler(["decode", "decode"], [pol])
    busy, idle = "127.0.0.1:9001", "127.0.0.1:9002"
    feed(pool, busy, "queue_depth", [0.5, 0.5])
    feed(pool, idle, "queue_depth", [0.0, 0.0])
    pool.replicas[busy].outstanding = 2
    assert decision(a.step(now=100.0), "decode").direction == "down"
    assert fleet.retired == [idle]


def test_failed_action_leaves_shape_and_loop_alive():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                     high=4.0, low=0.5, window=2, cooldown_up_s=0.0)
    pool = make_pool(["decode"])
    state = FakeState(pool)

    def bad_spawn(role):
        raise RuntimeError("no capacity")

    a = Autoscaler(state, bad_spawn, lambda rid: True, [pol])
    feed(pool, "127.0.0.1:9001", "queue_depth", [9, 9])
    d = decision(a.step(now=100.0), "decode")
    assert d.direction is None and d.reason == "action_failed"
    assert len(pool.replicas) == 1
    kinds = [e.get("kind") for e in state.flightrec.dump()["events"]]
    assert "scale_error" in kinds
    # next step still evaluates (and would act if spawn recovered)
    assert decision(a.step(now=101.0), "decode").reason in (
        "action_failed", "signal_high")


def test_replica_seconds_integral_and_stats():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3)
    a, pool, fleet, _ = make_scaler(["decode", "decode"], [pol])
    a.step(now=100.0)
    a.step(now=110.0)   # 2 replicas x 10s
    fleet.spawn("decode")
    a.step(now=115.0)   # 3 replicas x 5s
    assert a.replica_seconds == pytest.approx(2 * 10 + 3 * 5)
    s = a.stats()
    assert s["replica_seconds"] == pytest.approx(35.0)
    assert s["steps"] == 3


def test_autoscale_metrics_exported():
    pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                     high=4.0, low=0.5, window=2, cooldown_up_s=0.0)
    a, pool, fleet, state = make_scaler(["decode"], [pol])
    feed(pool, "127.0.0.1:9001", "queue_depth", [9, 9])
    a.step(now=100.0)
    a.step(now=101.0)
    text = state.registry.render()
    assert 'butterfly_fleet_autoscale_decisions_total{' in text
    assert 'tier="decode"' in text and 'direction="up"' in text
    assert "butterfly_fleet_autoscale_replica_seconds_total" in text


# ---------------------------------------------------------------------------
# live fleets (slow tier): spawn joins, retire drains, loop closes
# ---------------------------------------------------------------------------

PAGE = 8


def post_completion(url, prompt_tokens, max_new=4, timeout=60):
    body = json.dumps({"tokens": prompt_tokens, "max_tokens": max_new,
                       "stop_token": -1}).encode()
    req = urllib.request.Request(
        url + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_spawned_replica_joins_and_serves():
    from butterfly_tpu.fleet.harness import start_fleet
    fleet = start_fleet("1p1d", page_size=PAGE, max_batch=2, max_seq=128,
                        warm=True)
    try:
        h = fleet.spawn("decode")
        assert h.rid in fleet.state.pool.replicas
        assert fleet.state.pool.replicas[h.rid].role == "decode"
        assert h.rid in fleet.rids and len(fleet.replicas) == 3
        # the new member serves directly (it was warmed before joining)
        r = post_completion(h.url, [7] * 12)
        assert len(r["tokens"]) == 4
        # and the control plane routes across the grown pool
        r = post_completion(fleet.url, [7] * 12)
        assert len(r["tokens"]) == 4
    finally:
        fleet.stop()


def test_retire_drains_without_dropping_requests():
    """Shrink mid-traffic: every request issued before AND during the
    retire completes; the retired member leaves the pool."""
    from butterfly_tpu.fleet.harness import start_fleet
    fleet = start_fleet("3", page_size=PAGE, max_batch=2, max_seq=128,
                        warm=True)
    try:
        victim = fleet.rids[-1]
        results, errors = [], []

        def client(i):
            try:
                results.append(
                    post_completion(fleet.url, [3 + i % 5] * 10))
            except Exception as e:  # any drop fails the test
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        assert fleet.retire(victim, timeout=30.0)
        for t in threads:
            t.join(timeout=60.0)
        assert not errors
        assert len(results) == 8
        assert all(len(r["tokens"]) == 4 for r in results)
        assert victim not in fleet.state.pool.replicas
        assert victim not in fleet.rids
    finally:
        fleet.stop()


def test_autoscaler_closes_the_loop_on_a_live_fleet():
    """The full circuit: scraped rings -> policy -> spawn/retire on a
    real topology, both directions, decisions in the flight recorder."""
    import time as _time
    from butterfly_tpu.fleet.harness import start_fleet
    fleet = start_fleet("1p1d", page_size=PAGE, max_batch=2, max_seq=128,
                        warm=True, probe_interval=0.1)
    try:
        pol = TierPolicy("decode", min_replicas=1, max_replicas=2,
                         signal="queue_depth", high=0.5, low=0.1,
                         window=2, cooldown_up_s=0.0, cooldown_down_s=0.2)
        a = Autoscaler(fleet.state, fleet.spawn, fleet.retire, [pol])
        dec_rid = [r.rid for r in fleet.replicas if r.role == "decode"][0]
        # saturate the decode tier so scraped queue_depth rises
        stop = threading.Event()

        def pressure():
            while not stop.is_set():
                try:
                    post_completion(fleet.by_rid[dec_rid].url,
                                    [5] * 16, max_new=8)
                except Exception:
                    pass

        threads = [threading.Thread(target=pressure) for _ in range(4)]
        for t in threads:
            t.start()
        grew = False
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            if any(d.direction == "up" for d in a.step()):
                grew = True
                break
            _time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        assert grew, "autoscaler never grew the saturated decode tier"
        roles = [r.role for r in fleet.state.pool.replicas.values()]
        assert roles.count("decode") == 2

        # load gone: the tier shrinks back once rings show idle and the
        # hysteresis window passes
        shrank = False
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline:
            if any(d.direction == "down" for d in a.step()):
                shrank = True
                break
            _time.sleep(0.15)
        assert shrank, "autoscaler never shrank the idle decode tier"
        roles = [r.role for r in fleet.state.pool.replicas.values()]
        assert roles.count("decode") == 1
        # both decisions are auditable in the control-plane recorder
        kinds = [(e.get("kind"), e.get("direction"))
                 for e in fleet.state.flightrec.dump()["events"]]
        assert ("scale", "up") in kinds and ("scale", "down") in kinds
    finally:
        fleet.stop()


def autoscale_soak(loadgen):
    """A ramp-arrival open loop (2 -> 16 req/s over 4 s, then hold)
    against a 1p1d in-process fleet WITH the closed-loop autoscaler
    live on the decode tier: the fleet starts small and correct for the
    head of the ramp, the scraped queue-depth rings rise with the
    offered rate, and the loop must grow the decode tier mid-soak.
    After the load ends a settle window lets the hysteresis-guarded
    scale-down fire. Returns what the soak test asserts: the client's
    drops and SLO attainment, the scale-ups, the replica-seconds spent
    against a fleet held at the peak shape throughout, and the scale
    decisions served by /debug/flightrecorder."""
    import time

    from butterfly_tpu.fleet.harness import start_fleet

    max_tokens = 8
    slo_ttft_ms, slo_itl_ms = 10000.0, 2000.0
    shared_len = PAGE * 4
    tail = PAGE // 2
    fleet = start_fleet("1p1d", page_size=PAGE, max_batch=2,
                        max_seq=shared_len + tail + max_tokens + 16,
                        probe_interval=0.1,
                        slo_ttft_s=slo_ttft_ms / 1e3,
                        slo_itl_s=slo_itl_ms / 1e3,
                        warm_len=shared_len + tail)
    try:
        n0 = len(fleet.replicas)
        pol = TierPolicy("decode", min_replicas=1, max_replicas=3,
                         signal="queue_depth", high=0.5, low=0.05,
                         window=2, cooldown_up_s=0.5,
                         cooldown_down_s=1.0)
        scaler = Autoscaler(fleet.state, fleet.spawn, fleet.retire,
                            [pol], interval_s=0.2)
        scaler.start()
        t0 = time.monotonic()
        load = loadgen.run_load(fleet.url, clients=4,
                                requests_per_client=6, prefix_share=0.5,
                                shared_len=shared_len, tail_len=tail,
                                max_tokens=max_tokens, seed=0,
                                slo_ttft_ms=slo_ttft_ms,
                                slo_itl_ms=slo_itl_ms,
                                arrival="ramp:2:16:4")
        # settle: idle rings drain below the low band and the
        # hysteresis window elapses — the scale-down half of the claim
        deadline = time.monotonic() + 6.0
        while time.monotonic() < deadline:
            if scaler.stats()["scale_downs"] > 0:
                break
            time.sleep(0.2)
        wall = time.monotonic() - t0
        scaler.stop()
        st = scaler.stats()
        # replay the event log to find the peak shape the fleet reached
        peak = n = n0
        for e in st["events"]:
            n += 1 if e["direction"] == "up" else -1
            peak = max(peak, n)
        with urllib.request.urlopen(fleet.url + "/debug/flightrecorder",
                                    timeout=10.0) as resp:
            rec = json.loads(resp.read())
    finally:
        fleet.stop()
    return {
        "dropped": load["failed"],
        "slo_attainment": load.get("slo_attainment"),
        "scale_ups": st["scale_ups"],
        # the cost side: integral of live replicas over the soak vs a
        # static fleet provisioned at the peak shape the whole time
        "replica_seconds": st["replica_seconds"],
        "static_peak_replica_seconds": peak * wall,
        "flightrec_scale_events": sum(
            e.get("kind") == "scale" for e in rec.get("events", ())),
    }


def test_autoscale_benchmark_beats_static_peak(loadgen):
    """ISSUE 17 acceptance: ramp-arrival soak where the autoscaler
    holds SLO attainment at the objective while spending fewer
    replica-seconds than a static fleet provisioned at the peak shape,
    with the decisions auditable via /debug/flightrecorder."""
    out = autoscale_soak(loadgen)
    assert out["dropped"] == 0
    assert out["slo_attainment"] == 1.0
    assert out["scale_ups"] >= 1
    assert out["replica_seconds"] < out["static_peak_replica_seconds"]
    assert out["flightrec_scale_events"] >= 1


def test_parse_topology_arbitrary_shapes():
    from butterfly_tpu.fleet.harness import parse_topology
    assert parse_topology("2p2d") == ["prefill"] * 2 + ["decode"] * 2
    assert parse_topology("3p5d") == ["prefill"] * 3 + ["decode"] * 5
    assert parse_topology("0p4d") == ["decode"] * 4
    assert parse_topology("2p0d") == ["prefill"] * 2
    assert parse_topology(" 1P1D ") == ["prefill", "decode"]
    assert parse_topology("4") == ["both"] * 4
    for bad in ("0p0d", "0", "pd", "2p2", "x"):
        with pytest.raises(ValueError):
            parse_topology(bad)
