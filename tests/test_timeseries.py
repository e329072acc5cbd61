"""Metrics time series (ISSUE 16): the SignalRecorder ring, alert
rules, the fleet rollup timeline, and the stdlib dashboard.

Layers covered:

* rate derivation (``Counter.rate`` clamps at zero across a counter
  reset) and the registry's cheap ``snapshot()``;
* the recorder's ring bounds, ``since=`` pagination across a ring
  wrap, and the ``signals=`` filter (the /debug/timeseries contract);
* alert predicates — including THE mutcheck discriminator: a single
  above-threshold sample must NOT fire a sustained rule — rising-edge
  latching, and the flight-recorder ``alert`` events with series
  context;
* the scheduler soak: a tight page pool under load produces visibly
  MOVING preemption-rate and pages-free series plus a fired alert;
* the fleet merge: >= 3 sources on one clock, stale-gauge drop, the
  per-replica flatline rules;
* tools/dashboard.py + ``butterfly dash`` + tick_report ``--follow``
  subprocess/CLI smoke.
"""
import json
import subprocess
import sys
import threading
import urllib.request
from pathlib import Path

import pytest

from butterfly_tpu.obs.registry import (Counter, MetricsRegistry,
                                        parse_exposition)
from butterfly_tpu.obs.ticklog import FlightRecorder
from butterfly_tpu.obs.timeseries import (FLEET_TIMESERIES_SCHEMA,
                                          TIMESERIES_SCHEMA, AlertRule,
                                          SignalRecorder,
                                          default_fleet_rules,
                                          default_rules, evaluate_rules,
                                          series_summary,
                                          slope_per_sample)

REPO = Path(__file__).parent.parent


def samples_of(values, signal="s"):
    """Ring-entry dicts for one signal's value sequence."""
    return [{"seq": i, "signals": {signal: v}}
            for i, v in enumerate(values)]


# ---------------------------------------------------------------------------
# registry satellites: Counter.rate + snapshot + exposition edge cases
# ---------------------------------------------------------------------------

def test_counter_rate_and_reset_clamp():
    assert Counter.rate(10.0, 30.0, 2.0) == 10.0
    # counter reset (replica restart): clamped, never negative
    assert Counter.rate(100.0, 3.0, 1.0) == 0.0
    # degenerate dt never divides by zero
    assert Counter.rate(0.0, 5.0, 0.0) == 0.0
    assert Counter.rate(0.0, 5.0, -1.0) == 0.0


def test_registry_snapshot_cheap_values():
    reg = MetricsRegistry()
    reg.counter("reqs_total").inc(3)
    reg.gauge("depth").set(7)
    fam = reg.counter_family("by_kind_total", "", ("kind",))
    fam.labels("a").inc(2)
    fam.labels("b").inc(5)
    snap = reg.snapshot()
    assert snap["reqs_total"] == 3.0
    assert snap["depth"] == 7.0
    # labeled families collapse to their sum (a scalar trajectory)
    assert snap["by_kind_total"] == 7.0


def test_zero_observation_histogram_exposition_parses():
    """A histogram with zero observations still renders its full
    ladder, and the parse roundtrip keeps +Inf == _count == 0 (the
    fleet rollup must not choke on a fresh replica)."""
    reg = MetricsRegistry()
    reg.histogram("ttft_seconds", "help", (0.1, 1.0))
    fams = parse_exposition(reg.render())
    h = fams["butterfly_ttft_seconds"]
    inf = h["samples"][("butterfly_ttft_seconds_bucket", (("le", "+Inf"),))]
    assert inf == h["samples"][("butterfly_ttft_seconds_count", ())] == 0.0
    assert h["samples"][("butterfly_ttft_seconds_sum", ())] == 0.0
    # the finite ladder is present even with nothing observed
    assert ("butterfly_ttft_seconds_bucket",
            (("le", "0.1"),)) in h["samples"]


# ---------------------------------------------------------------------------
# the recorder ring
# ---------------------------------------------------------------------------

def test_recorder_rejects_disabled_interval():
    with pytest.raises(ValueError):
        SignalRecorder(interval_s=0.0)


def test_recorder_due_gate():
    rec = SignalRecorder(interval_s=3600.0)
    assert rec.due()  # first sample is owed immediately
    rec.sample({"g": 1.0})
    assert not rec.due()  # next one is an hour away
    rec2 = SignalRecorder(interval_s=1e-9)
    rec2.sample({"g": 1.0})
    assert rec2.due()


def test_recorder_ring_bounded_and_seq_monotonic():
    rec = SignalRecorder(interval_s=1e-9, capacity=4)
    for i in range(7):
        rec.sample({"g": float(i)})
    d = rec.dump()
    assert d["schema"] == TIMESERIES_SCHEMA and d["enabled"] is True
    seqs = [s["seq"] for s in d["samples"]]
    assert seqs == [3, 4, 5, 6]  # oldest evicted, order preserved
    assert d["next_seq"] == 7


def test_recorder_rates_from_cumulative_counters():
    rec = SignalRecorder(interval_s=1e-9)
    rec.sample({}, rates={"tok_ps": 100.0})
    rec.sample({}, rates={"tok_ps": 160.0})
    s1, s2 = rec.dump()["samples"]
    assert s1["signals"]["tok_ps"] == 0.0  # no prior delta yet
    dt = s2["t_mono"] - s1["t_mono"]
    assert s2["signals"]["tok_ps"] == pytest.approx(60.0 / dt)
    # counter reset between samples: the rate clamps flat at zero
    rec.sample({}, rates={"tok_ps": 3.0})
    assert rec.dump()["samples"][-1]["signals"]["tok_ps"] == 0.0


def test_dump_since_pagination_across_ring_wrap():
    rec = SignalRecorder(interval_s=1e-9, capacity=4)
    for i in range(6):
        rec.sample({"g": float(i)})
    # a cursor older than the ring tail returns what survived the wrap
    assert [s["seq"] for s in rec.dump(since=0)["samples"]] == [2, 3, 4, 5]
    assert [s["seq"] for s in rec.dump(since=4)["samples"]] == [4, 5]
    # the incremental-poll contract: since=next_seq is empty, not an error
    nxt = rec.dump()["next_seq"]
    assert rec.dump(since=nxt)["samples"] == []


def test_dump_signals_filter():
    rec = SignalRecorder(interval_s=1e-9)
    rec.sample({"a": 1.0, "b": 2.0, "c": 3.0})
    d = rec.dump(signals=["a", "c"])
    assert d["samples"][0]["signals"] == {"a": 1.0, "c": 3.0}
    # unfiltered dump unaffected
    assert set(rec.dump()["samples"][0]["signals"]) == {"a", "b", "c"}


def test_sample_carries_caller_wall_stamp():
    rec = SignalRecorder(interval_s=1e-9)
    rec.sample({"g": 1.0}, t_wall=1234.5)
    assert rec.dump()["samples"][0]["t_wall"] == 1234.5


# ---------------------------------------------------------------------------
# alert rules
# ---------------------------------------------------------------------------

def test_alert_rule_validation():
    with pytest.raises(ValueError):
        AlertRule("x", "s", 3, "sideways", 1.0)
    with pytest.raises(ValueError):
        AlertRule("x", "s", 0, "sustained_above", 1.0)


def test_slope_per_sample():
    assert slope_per_sample([0.0, 1.0, 2.0, 3.0]) == pytest.approx(1.0)
    assert slope_per_sample([9.0, 7.0, 5.0]) == pytest.approx(-2.0)
    assert slope_per_sample([5.0]) == 0.0
    assert slope_per_sample([]) == 0.0


def test_sustained_single_sample_does_not_fire():
    """THE mutcheck discriminator: one above-threshold sample is a
    blip, not an alert — the window-length guard must hold."""
    rule = AlertRule("burn", "s", 5, "sustained_above", 0.5)
    assert evaluate_rules([rule], samples_of([0.9])) == []
    # even several hot samples short of the window stay silent
    assert evaluate_rules([rule], samples_of([0.9] * 4)) == []


def test_sustained_fires_after_window_and_latches():
    rule = AlertRule("burn", "s", 3, "sustained_above", 0.5,
                     severity="page")
    fired = evaluate_rules([rule], samples_of([0.9, 0.8, 0.7]))
    assert len(fired) == 1
    rec = fired[0]
    assert rec["rule"] == "burn" and rec["severity"] == "page"
    assert rec["value"] == 0.7 and rec["series"] == [0.9, 0.8, 0.7]
    # still hot: same excursion, no repeat alert
    assert evaluate_rules([rule], samples_of([0.9, 0.8, 0.7, 0.6])) == []
    # predicate releases (one cool sample), then a fresh excursion fires
    assert evaluate_rules([rule], samples_of([0.7, 0.6, 0.1])) == []
    assert len(evaluate_rules([rule],
                              samples_of([0.1, 0.9, 0.9, 0.9]))) == 1


def test_drift_above_needs_two_windows():
    rule = AlertRule("drift", "s", 3, "drift_above", 0.5)
    # recent mean 2.0 vs prior mean 1.0: drift 1.0 > 0.5
    vals = [1.0, 1.0, 1.0, 2.0, 2.0, 2.0]
    fired = evaluate_rules([rule], samples_of(vals))
    assert len(fired) == 1 and fired[0]["value"] == pytest.approx(1.0)
    # only one window of history: silent
    rule2 = AlertRule("drift", "s", 3, "drift_above", 0.5)
    assert evaluate_rules([rule2], samples_of([2.0, 2.0, 2.0])) == []


def test_slope_below_fires_on_draining_series():
    rule = AlertRule("drain", "s", 4, "slope_below", -1.0)
    fired = evaluate_rules([rule], samples_of([40.0, 30.0, 20.0, 10.0]))
    assert len(fired) == 1
    assert fired[0]["value"] == pytest.approx(-10.0)
    rule2 = AlertRule("drain", "s", 4, "slope_below", -1.0)
    assert evaluate_rules([rule2],
                          samples_of([10.0, 10.1, 10.0, 10.1])) == []


def test_flatline_counts_missing_not_series():
    rule = AlertRule("flat", "scrape", 3, "flatline", 3)
    assert evaluate_rules([rule], [], missing=2) == []
    fired = evaluate_rules([rule], [], missing=3)
    assert len(fired) == 1 and fired[0]["value"] == 3.0
    # latched while missing, re-arms once the source reappears
    assert evaluate_rules([rule], [], missing=4) == []
    assert evaluate_rules([rule], [], missing=0) == []
    assert len(evaluate_rules([rule], [], missing=3)) == 1


def test_alert_event_lands_in_flightrec_with_series():
    fr = FlightRecorder()
    rule = AlertRule("burn", "s", 2, "sustained_above", 0.5)
    evaluate_rules([rule], samples_of([0.9, 0.9]), flightrec=fr,
                   source="rep1")
    evs = [e for e in fr.dump()["events"] if e["kind"] == "alert"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["rule"] == "burn" and ev["source"] == "rep1"
    assert ev["series"] == [0.9, 0.9]  # the post-mortem context
    assert "t_wall" in ev


def test_recorder_collects_alerts_in_dump():
    rec = SignalRecorder(
        interval_s=1e-9,
        rules=[AlertRule("burn", "g", 2, "sustained_above", 0.5)])
    rec.sample({"g": 0.9}, t_wall=10.0)
    fired = rec.sample({"g": 0.9}, t_wall=11.0)
    assert len(fired) == 1
    alerts = rec.dump()["alerts"]
    assert len(alerts) == 1
    assert alerts[0]["rule"] == "burn" and alerts[0]["t_wall"] == 11.0
    assert alerts[0]["seq"] == 1


def test_default_rule_tables():
    names = {r.name for r in default_rules()}
    assert names == {"slo_burn_sustained", "host_frac_drift",
                     "pages_free_slope"}
    fleet = {r.name for r in default_fleet_rules()}
    assert "replica_flatline" in fleet
    # described in the dump so a dashboard can render the rule table
    rec = SignalRecorder(interval_s=1e-9, rules=default_rules())
    assert {r["rule"] for r in rec.dump()["rules"]} == names


def test_series_summary_shape_scalars():
    rec = SignalRecorder(interval_s=1e-9)
    for v in (1.0, 3.0, 5.0):
        rec.sample({"g": v, "h": 2.0})
    summ = series_summary(rec.dump())
    assert summ["g"]["peak"] == 5.0
    assert summ["g"]["mean"] == pytest.approx(3.0)
    assert summ["g"]["slope"] == pytest.approx(2.0)
    assert summ["g"]["n"] == 3.0
    assert summ["h"]["slope"] == 0.0
    assert series_summary(rec.dump(), signals=["h"]).keys() == {"h"}


# ---------------------------------------------------------------------------
# scheduler integration: the tight-pool soak
# ---------------------------------------------------------------------------

def _make_sched(**kw):
    import jax
    from butterfly_tpu.core.config import RuntimeConfig, tiny
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(42))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4,
                       num_pages=6)
    return Scheduler(ServingEngine(model, params, rt), **kw)


def test_recorder_off_is_zero_cost_default():
    """No recorder attached: the scheduler's only timeseries state is
    the None attribute (the per-tick cost is one is-None check; the
    phase-reconciliation suite runs entirely in this mode)."""
    sched = _make_sched()
    assert sched.timeseries is None
    sched.submit([5, 7, 11], max_new_tokens=3)
    sched.run_until_done()


def test_scheduler_soak_moving_series_and_alert():
    """The acceptance soak: a tight page pool under competing
    generations yields NON-CONSTANT pages-free and preemption-rate
    series, and an alert fires into the flight recorder with its
    series context attached."""
    fr = FlightRecorder()
    rec = SignalRecorder(
        interval_s=1e-9, capacity=4096, flightrec=fr,
        rules=[
            # fires when the pool drains across a window — the natural
            # trajectory of two growing requests over 6 pages
            AlertRule("pool_draining", "kv_pages_free", 3,
                      "slope_below", -0.01),
            # guaranteed excursion: two consecutive busy samples
            AlertRule("busy", "active_requests", 2,
                      "sustained_above", 0.5),
        ])
    sched = _make_sched(flightrec=fr, timeseries=rec)
    r1 = sched.submit([5, 7, 11], max_new_tokens=10)
    r2 = sched.submit([3, 1], max_new_tokens=10)
    sched.run_until_done(max_ticks=300)
    assert r1.state == "finished" and r2.state == "finished"
    assert sched.metrics()["preemptions_total"] > 0

    d = rec.dump()
    assert len(d["samples"]) >= 10
    pages = [s["signals"]["kv_pages_free"] for s in d["samples"]]
    assert len(set(pages)) > 1  # visibly moving, not a flat line
    pre = [s["signals"]["preemptions_per_sec"] for s in d["samples"]]
    assert max(pre) > 0.0 and len(set(pre)) > 1
    # every sample speaks the full signal vocabulary
    assert {"queue_depth", "active_requests", "inflight_depth",
            "kv_pages_free", "tokens_per_sec",
            "preemptions_per_sec"} <= set(d["samples"][0]["signals"])
    # an alert fired and the flight recorder holds it with context
    assert d["alerts"]
    evs = [e for e in fr.dump()["events"] if e["kind"] == "alert"]
    assert evs and "series" in evs[0]


def test_server_debug_timeseries_endpoint():
    """GET /debug/timeseries end to end: enabled body with samples,
    since/signals query params, and the disabled shape."""
    from http.server import ThreadingHTTPServer
    from butterfly_tpu.serve.server import ServerState, make_handler
    from butterfly_tpu.utils.tokenizer import ByteTokenizer
    rec = SignalRecorder(interval_s=1e-9, rules=default_rules())
    sched = _make_sched(timeseries=rec)
    state = ServerState(sched, ByteTokenizer())
    state.thread.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_port}"
    try:
        body = json.dumps({"tokens": [5, 6, 7], "max_tokens": 4,
                           "stop_token": -1}).encode()
        req = urllib.request.Request(
            url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=120).read()
        d = json.loads(urllib.request.urlopen(
            url + "/debug/timeseries", timeout=30).read())
        assert d["enabled"] and d["schema"] == TIMESERIES_SCHEMA
        assert d["samples"] and d["rules"]
        nxt = d["next_seq"]
        d2 = json.loads(urllib.request.urlopen(
            url + f"/debug/timeseries?since={nxt}&signals=queue_depth",
            timeout=30).read())
        # the scheduler thread may still be ticking, so the incremental
        # poll can legitimately see fresh samples — but never a replay
        # of anything at or before the cursor
        assert all(s["seq"] >= nxt for s in d2["samples"])
        d3 = json.loads(urllib.request.urlopen(
            url + "/debug/timeseries?signals=queue_depth,kv_pages_free",
            timeout=30).read())
        assert set(d3["samples"][0]["signals"]) <= {"queue_depth",
                                                    "kv_pages_free"}
    finally:
        state.stop.set()
        httpd.shutdown()
    # a scheduler without a recorder serves the disabled shape
    from butterfly_tpu.serve.server import ServerState as SS
    state2 = SS(_make_sched(), ByteTokenizer())
    assert state2.debug_timeseries() == {"enabled": False,
                                         "samples": [], "alerts": []}


# ---------------------------------------------------------------------------
# fleet: scrape rings, stale-gauge drop, merged timeline
# ---------------------------------------------------------------------------

def _gauge_text(**gauges):
    lines = []
    for name, v in gauges.items():
        lines.append(f"# TYPE butterfly_{name} gauge")
        lines.append(f"butterfly_{name} {v}")
    lines.append("# TYPE butterfly_reqs_total counter")
    lines.append("butterfly_reqs_total 5")
    return "\n".join(lines) + "\n"


def test_flat_gauges_extracts_unlabeled_gauges():
    from butterfly_tpu.router.pool import _flat_gauges
    text = (_gauge_text(queue_depth=3, kv_pages_free=40)
            + "# TYPE butterfly_out gauge\n"
            + 'butterfly_out{replica="a"} 2\n')
    flat = _flat_gauges(parse_exposition(text))
    # prefix stripped; counters and labeled families skipped
    assert flat == {"queue_depth": 3.0, "kv_pages_free": 40.0}


class _StubReplica:
    """Minimal /health + /metrics HTTP stub for pool-probe tests."""

    def __init__(self):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        import time as _time
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                if self.path == "/health":
                    body = json.dumps(
                        {"status": "ok", "queue_depth": 1, "active": 1,
                         "free_pages": stub.free_pages,
                         "now_wall": _time.time()}).encode()
                    ctype = "application/json"
                else:
                    body = _gauge_text(
                        queue_depth=1,
                        kv_pages_free=stub.free_pages).encode()
                    ctype = "text/plain"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.free_pages = 40
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.rid = f"127.0.0.1:{self.httpd.server_port}"


def test_pool_probe_appends_series_and_tracks_scrape_fails():
    from butterfly_tpu.router.pool import ReplicaPool
    stub = _StubReplica()
    seen = []
    pool = ReplicaPool([stub.rid], scrape_metrics=True,
                       probe_timeout=5.0)
    pool.on_series_sample = lambda rid, tail, missed: seen.append(
        (rid, len(tail), missed))
    r = pool.replicas[stub.rid]
    pool.probe_one(r)
    stub.free_pages = 38
    pool.probe_one(r)
    ring = pool.series_by_replica()[stub.rid]
    assert [s["signals"]["kv_pages_free"] for s in ring] == [40.0, 38.0]
    assert all("t_wall" in s for s in ring)
    assert r.scrape_fails == 0 and pool.stale_scrapes(1) == []
    # observer called outside the lock with the tail + failure count
    assert seen == [(stub.rid, 1, 0), (stub.rid, 2, 0)]
    # kill the replica: probes fail, the stale counter climbs, the
    # last-good series survives for the merge
    stub.httpd.shutdown()
    stub.httpd.server_close()
    for _ in range(3):
        pool.probe_one(r)
    assert r.scrape_fails >= 3
    assert pool.stale_scrapes(3) == [stub.rid]
    assert len(pool.series_by_replica()[stub.rid]) == 2
    assert seen[-1][2] >= 3


def _control_state(backends):
    from butterfly_tpu.fleet.controlplane import ControlPlaneState
    from butterfly_tpu.router.policy import PrefixAffinityPolicy
    from butterfly_tpu.router.pool import ReplicaPool
    pool = ReplicaPool(backends, scrape_metrics=True, probe_timeout=0.5)
    return ControlPlaneState(pool, PrefixAffinityPolicy(pool))


def test_fleet_metrics_text_drops_stale_gauges():
    state = _control_state(["127.0.0.1:1", "127.0.0.1:2"])
    for rid in state.pool.replicas:
        state.pool.replicas[rid].metrics_families = parse_exposition(
            _gauge_text(queue_depth=3, kv_pages_free=40))
    state.pool.replicas["127.0.0.1:2"].scrape_fails = \
        state.SCRAPE_STALE_AFTER
    text = state.fleet_metrics_text()
    # the fresh replica's gauges re-export; the stale one's are dropped
    assert ('butterfly_fleet_replica_queue_depth{replica="127.0.0.1:1"}'
            in text)
    assert 'replica="127.0.0.1:2"' not in text
    # counter sums still include BOTH replicas' last good scrape
    fams = parse_exposition(text)
    assert fams["butterfly_fleet_reqs_total"]["samples"][
        ("butterfly_fleet_reqs_total", ())] == 10.0


def test_fleet_timeseries_merges_three_sources_on_one_clock():
    state = _control_state(
        ["127.0.0.1:1", "127.0.0.1:2", "127.0.0.1:3"])
    for i, rid in enumerate(sorted(state.pool.replicas)):
        r = state.pool.replicas[rid]
        r.clock_offset = float(i)  # learned probe offsets
        for k in range(3):
            r.series.append({"t_wall": 100.0 + 10 * i + k,
                             "signals": {"kv_pages_free": 40.0 - k}})
    # a control-plane alert event rides along in the merged view
    state.flightrec.note("alert", rule="replica_flatline",
                         signal="scrape", source="127.0.0.1:3",
                         severity="page", value=3.0, series=[])
    d = state.fleet_timeseries()
    assert d["schema"] == FLEET_TIMESERIES_SCHEMA
    scrape_srcs = [s for s in d["sources"] if s.startswith("scrape:")]
    assert len(scrape_srcs) == 3  # >= 3 sources merged
    assert all(d["sources"][s]["samples"] == 3 for s in scrape_srcs)
    # unreachable replicas degrade to an error entry, never a 500
    assert all(d["sources"][rid].get("missing")
               for rid in state.pool.replicas)
    # one clock: scrape rings merge at offset zero, ordered by t_fleet
    ts = [s["t_fleet"] for s in d["samples"]]
    assert ts == sorted(ts) and len(ts) == 9
    assert all(s["t_fleet"] == s["t_wall"] for s in d["samples"])
    assert [a["rule"] for a in d["alerts"]] == ["replica_flatline"]
    json.dumps(d)  # the endpoint body must be JSON-clean


def test_control_plane_flatline_rules_per_replica():
    state = _control_state(["127.0.0.1:1", "127.0.0.1:2"])
    # three consecutive missed scrapes: the per-replica rule pages once
    state._on_series_sample("127.0.0.1:1", [], 3)
    state._on_series_sample("127.0.0.1:1", [], 4)  # latched, no repeat
    state._on_series_sample("127.0.0.1:2", [], 3)  # its OWN rule set
    evs = [e for e in state.flightrec.dump()["events"]
           if e["kind"] == "alert"]
    assert [(e["rule"], e["source"]) for e in evs] == \
        [("replica_flatline", "127.0.0.1:1"),
         ("replica_flatline", "127.0.0.1:2")]


# ---------------------------------------------------------------------------
# dashboard + CLI smoke
# ---------------------------------------------------------------------------

def _replica_dump_file(tmp_path):
    rec = SignalRecorder(
        interval_s=1e-9,
        rules=[AlertRule("busy", "queue_depth", 2,
                         "sustained_above", 0.5)])
    for i in range(12):
        rec.sample({"queue_depth": float(i % 5),
                    "kv_pages_free": 40.0 - i}, t_wall=100.0 + i)
    path = tmp_path / "ts.json"
    path.write_text(json.dumps(rec.dump()))
    return path


def _fleet_dump_file(tmp_path):
    samples = [{"seq": i, "t_wall": 100.0 + i, "t_fleet": 100.0 + i,
                "source": src, "signals": {"kv_pages_free": 40.0 - i}}
               for src in ("scrape:a:1", "a:1", "scrape:b:2")
               for i in range(6)]
    dump = {"schema": FLEET_TIMESERIES_SCHEMA,
            "sources": {"scrape:a:1": {"samples": 6}},
            "samples": samples,
            "alerts": [{"rule": "pages_free_slope",
                        "signal": "kv_pages_free", "severity": "warn",
                        "source": "a:1", "value": -1.5, "window": 8,
                        "t_fleet": 103.0}]}
    path = tmp_path / "fleet.json"
    path.write_text(json.dumps(dump))
    return path


def test_dashboard_subprocess_smoke(tmp_path):
    dash = str(REPO / "tools" / "dashboard.py")
    rep = _replica_dump_file(tmp_path)
    out = subprocess.run([sys.executable, dash, str(rep)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "<svg" in out.stdout and "kv_pages_free" in out.stdout
    assert "replica timeseries" in out.stdout
    assert "alerts" in out.stdout  # the busy rule fired in the window

    txt = subprocess.run([sys.executable, dash, str(rep), "--text"],
                         capture_output=True, text=True, timeout=60)
    assert txt.returncode == 0, txt.stderr
    assert "kv_pages_free" in txt.stdout and "[warn]" in txt.stdout
    assert "window covered" in txt.stdout  # reconciliation footer

    fleet = _fleet_dump_file(tmp_path)
    fout = subprocess.run(
        [sys.executable, dash, str(fleet), "--out",
         str(tmp_path / "fleet.html")],
        capture_output=True, text=True, timeout=60)
    assert fout.returncode == 0, fout.stderr
    html = (tmp_path / "fleet.html").read_text()
    # per-source small multiples + alert annotations
    assert "scrape:a:1" in html and "scrape:b:2" in html
    assert "pages_free_slope" in html and 'class="alert"' in html

    bad = subprocess.run([sys.executable, dash,
                          str(tmp_path / "nope.json")],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 2 and "error:" in bad.stderr


def test_dashboard_scale_annotations_and_tier_panel(tmp_path):
    """--flightrecorder overlays kind=scale events as markers + a
    listing, and kv_tier_* signals render as their own panel with the
    hit rate on top (ISSUE 17)."""
    dash = str(REPO / "tools" / "dashboard.py")
    rec = SignalRecorder(interval_s=1e-9)
    for i in range(10):
        rec.sample({"queue_depth": float(i),
                    "kv_tier_hit_rate": 0.1 * i,
                    "kv_tier_pages_saved_total": float(2 * i)},
                   t_wall=100.0 + i)
    ts = tmp_path / "ts.json"
    ts.write_text(json.dumps(rec.dump()))
    fr = tmp_path / "fr.json"
    fr.write_text(json.dumps({"enabled": True, "events": [
        {"seq": 1, "t_wall": 103.0, "kind": "scale", "tier": "decode",
         "direction": "up", "reason": "signal_high",
         "n_before": 1, "n_after": 2},
        {"seq": 2, "t_wall": 104.0, "kind": "tick"},  # not a scale
        {"seq": 3, "t_wall": 108.0, "kind": "scale", "tier": "decode",
         "direction": "down", "reason": "signal_low",
         "n_before": 2, "n_after": 1},
    ]}))

    out = subprocess.run(
        [sys.executable, dash, str(ts), "--flightrecorder", str(fr)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.count('class="scale"') >= 2  # both in-window marks
    assert "2 scale event(s)" in out.stdout
    assert "decode up (signal_high) 1 -&gt; 2" in out.stdout
    # the tier panel exists and leads with the hit rate
    assert "<h3 class='panel'>kv tier</h3>" in out.stdout
    assert (out.stdout.index("kv_tier_hit_rate")
            < out.stdout.index("kv_tier_pages_saved_total"))

    txt = subprocess.run(
        [sys.executable, dash, str(ts), "--flightrecorder", str(fr),
         "--text"],
        capture_output=True, text=True, timeout=60)
    assert txt.returncode == 0, txt.stderr
    assert "scale events:" in txt.stdout
    assert "+3.0s decode up (signal_high) 1 -> 2" in txt.stdout
    assert "+8.0s decode down (signal_low) 2 -> 1" in txt.stdout
    assert "-- kv tier --" in txt.stdout

    bad = subprocess.run(
        [sys.executable, dash, str(ts), "--flightrecorder",
         str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=60)
    assert bad.returncode == 2 and "error:" in bad.stderr


def test_butterfly_dash_cli(tmp_path, capsys):
    from butterfly_tpu.serve.cli import main
    rep = _replica_dump_file(tmp_path)
    assert main(["dash", str(rep), "--text"]) == 0
    out = capsys.readouterr().out
    assert "kv_pages_free" in out and "timeseries" in out
    html_path = tmp_path / "d.html"
    assert main(["dash", str(rep), "--out", str(html_path)]) == 0
    assert "<svg" in html_path.read_text()


def test_tick_report_follow_polls_since(tmp_path, capsys):
    """--follow against a stub /debug/ticks?since= server: renders
    each tick once, advances the cursor, stops at --max-polls."""
    import importlib.util
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    ticks = [{"seq": i, "wall_s": 0.01,
              "phases": {"dispatch": 0.004, "drain": 0.002},
              "fetch_s": 0.001, "batch": 2, "waiting": 0,
              "inflight": 1, "pages_free": 9, "generated": 2,
              "barrier_causes": []} for i in range(5)]
    cursors = []

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            since = int(self.path.rpartition("=")[2])
            cursors.append(since)
            body = json.dumps(
                {"enabled": True, "next_seq": 5,
                 "ticks": [t for t in ticks
                           if t["seq"] >= since]}).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        spec = importlib.util.spec_from_file_location(
            "tick_report", REPO / "tools" / "tick_report.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        rc = mod.main([f"http://127.0.0.1:{httpd.server_port}",
                       "--follow", "--interval", "0.01",
                       "--max-polls", "3"])
    finally:
        httpd.shutdown()
    assert rc == 0
    out = capsys.readouterr().out
    # all 5 ticks rendered exactly once, then the cursor caught up
    assert out.count("tick ") == 5
    assert "dom=dispatch" in out
    assert cursors == [0, 5, 5]
