"""Observability-layer tests: metrics registry exposition format,
trace ring-buffer semantics, and the tools/trace_report.py smoke run.

All jax-free (registry/trace are stdlib-only) so they run in any
environment the suite does, including JAX_PLATFORMS=cpu CI.
"""
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from butterfly_tpu.obs.metrics import render_prometheus
from butterfly_tpu.obs.registry import (
    LATENCY_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
    parse_exposition, render_parsed, sanitize_name, sum_expositions)
from butterfly_tpu.obs.trace import (
    Tracer, merge_fleet_trace, summarize_timeline)

REPO = Path(__file__).parent.parent


# -- registry ---------------------------------------------------------------

def test_counter_monotonic():
    c = Counter("reqs", "h")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = Gauge("depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6


def test_histogram_buckets_cumulative_and_consistent():
    h = Histogram("lat", "h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    cum, s, c = h.snapshot()
    # cumulative per-le counts: <=0.1 ->1, <=1 ->3, <=10 ->4, +Inf ->5
    assert cum == [1, 3, 4, 5]
    assert cum == sorted(cum), "bucket series must be monotonic"
    assert c == 5 and cum[-1] == c, "+Inf bucket must equal _count"
    assert s == pytest.approx(0.05 + 0.5 + 0.5 + 5.0 + 50.0)


def test_histogram_rejects_bad_buckets():
    for bad in ((), (1.0, 1.0), (2.0, 1.0)):
        with pytest.raises(ValueError):
            Histogram("h", buckets=bad)


def test_histogram_render_format():
    h = Histogram("ttft_seconds", "ttft", buckets=(0.5, 2.0))
    h.observe(0.3)
    h.observe(1.0)
    h.observe(99.0)
    lines = h.render("butterfly")
    assert "# HELP butterfly_ttft_seconds ttft" in lines
    assert "# TYPE butterfly_ttft_seconds histogram" in lines
    assert 'butterfly_ttft_seconds_bucket{le="0.5"} 1' in lines
    assert 'butterfly_ttft_seconds_bucket{le="2"} 2' in lines
    assert 'butterfly_ttft_seconds_bucket{le="+Inf"} 3' in lines
    assert "butterfly_ttft_seconds_sum 100.3" in lines
    assert "butterfly_ttft_seconds_count 3" in lines
    # bucket lines come before _sum/_count, bounds in ascending order
    text = "\n".join(lines)
    assert text.index('le="0.5"') < text.index('le="2"') \
        < text.index('le="+Inf"') < text.index("_sum")


def test_registry_get_or_create_idempotent():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "help")
    b = reg.counter("x_total")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # same name, different type


def test_name_sanitization():
    assert sanitize_name("a.b-c d") == "a_b_c_d"
    assert sanitize_name("0abc").startswith("_")
    reg = MetricsRegistry()
    c = reg.counter("bad.name-1")
    c.inc()
    out = reg.render()
    assert "butterfly_bad_name_1 1" in out
    # every exposed sample line is a legal prometheus series
    for line in out.splitlines():
        if line.startswith("#") or not line:
            continue
        assert re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$", line), \
            line


def test_render_prometheus_registry_wins_name_collisions():
    reg = MetricsRegistry()
    reg.counter("requests_total", "from registry").inc(7)
    reg.histogram("ttft_seconds", "ttft", buckets=LATENCY_BUCKETS)
    text = render_prometheus({"requests_total": 3, "queue_depth": 2},
                             registry=reg)
    # the dict copy of the colliding name is suppressed: exactly one
    # requests_total sample line, carrying the registry's value
    samples = [l for l in text.splitlines()
               if l.startswith("butterfly_requests_total ")]
    assert samples == ["butterfly_requests_total 7"]
    assert "butterfly_queue_depth 2" in text
    assert "butterfly_ttft_seconds_bucket" in text


def test_render_prometheus_plain_dict_unchanged():
    text = render_prometheus({"tokens_generated_total": 5})
    assert "# TYPE butterfly_tokens_generated_total counter" in text
    assert "butterfly_tokens_generated_total 5" in text


def test_render_prometheus_string_annotation_becomes_comment():
    """A string-valued entry must not crash the exposition renderer:
    it rides as a comment line the text-format parsers (including
    parse_prometheus) ignore."""
    text = render_prometheus({
        "drain_barriers_total": 1.0,
        "drain_barriers_note": "one barrier, cause page_pressure",
    })
    assert "butterfly_drain_barriers_total 1" in text
    assert "# butterfly_drain_barriers_note: one barrier" in text
    for line in text.splitlines():
        if not line.startswith("#"):
            float(line.rsplit(None, 1)[1])  # every sample parses


# -- tracer -----------------------------------------------------------------

def test_tracer_timeline_roundtrip():
    tr = Tracer()
    tr.begin_request(1, request_id="client-abc", prompt_len=3)
    tr.event(1, "admit", slot=0, queue_wait_s=0.01)
    tr.event(1, "first_token", ttft_s=0.02)
    tr.event(1, "finish", state="finished", tokens=4)
    tl = tr.timeline(1)
    assert tl["request_id"] == "client-abc"
    assert tl["done"] is True
    names = [e["name"] for e in tl["events"]]
    assert names == ["submit", "admit", "first_token", "finish"]
    ts = [e["t"] for e in tl["events"]]
    assert ts == sorted(ts)


def test_tracer_bounds_requests_and_events():
    tr = Tracer(max_requests=2, max_events_per_request=3)
    for rid in range(4):
        tr.begin_request(rid)
    assert [t["id"] for t in tr.timelines()] == [2, 3]
    for _ in range(10):
        tr.event(3, "decode")
    assert len(tr.timeline(3)["events"]) == 3
    # events for evicted/unknown requests are dropped, not resurrected
    tr.event(0, "late")
    assert tr.timeline(0) is None


def test_tracer_global_ring():
    tr = Tracer(max_global_events=4)
    for i in range(10):
        tr.event(None, "engine.table_sync", batch=i)
    evs = tr.global_events()
    assert len(evs) == 4
    assert [e["batch"] for e in evs] == [6, 7, 8, 9]


def test_summarize_timeline_phases():
    tr = Tracer()
    tr.begin_request(7, request_id="r7")
    tr.event(7, "admit", slot=0)
    tr.event(7, "prefill_chunk", start=0, tokens=8)
    tr.event(7, "prefill_done", tokens=8)
    tr.event(7, "first_token", ttft_s=0.1)
    tr.event(7, "finish", state="finished", tokens=5)
    s = summarize_timeline(tr.timeline(7))
    assert s["id"] == 7 and s["request_id"] == "r7"
    assert s["state"] == "finished" and s["tokens"] == 5
    assert s["prefill_chunks"] == 1 and s["preemptions"] == 0
    for k in ("queue_wait_s", "prefill_s", "ttft_s", "decode_s", "total_s"):
        assert s[k] is not None and s[k] >= 0
    # partial timeline: missing phases are None, not fabricated zeros
    tr.begin_request(8)
    s8 = summarize_timeline(tr.timeline(8))
    assert s8["ttft_s"] is None and s8["total_s"] is None
    assert s8["state"] == "live"


def test_tracer_dump_is_json_serializable():
    tr = Tracer()
    tr.begin_request(0, request_id=None)
    tr.event(0, "finish", state="finished", tokens=1)
    tr.event(None, "engine.table_sync")
    blob = json.dumps(tr.dump())
    back = json.loads(blob)
    assert back["requests"][0]["id"] == 0
    assert back["global_events"][0]["name"] == "engine.table_sync"


# -- exposition parsing + fleet aggregation ---------------------------------

def _registry_with(n, ladder=(0.1, 1.0)):
    reg = MetricsRegistry()
    reg.counter("requests_total", "Requests").inc(n)
    h = reg.histogram("ttft_seconds", "ttft", buckets=ladder)
    h.observe(0.05)
    h.observe(0.5)
    reg.gauge("queue_depth", "q").set(n)
    reg.counter_family("router_requests_total", "by",
                       ("replica",)).labels(f"r{n}").inc(n)
    return reg


def test_parse_exposition_roundtrip():
    fams = parse_exposition(_registry_with(3).render())
    assert fams["butterfly_requests_total"]["type"] == "counter"
    assert fams["butterfly_requests_total"]["samples"][
        ("butterfly_requests_total", ())] == 3.0
    # histogram series fold under the family name
    h = fams["butterfly_ttft_seconds"]
    assert h["type"] == "histogram"
    assert h["samples"][("butterfly_ttft_seconds_count", ())] == 2.0
    assert h["samples"][
        ("butterfly_ttft_seconds_bucket", (("le", "0.1"),))] == 1.0
    # labeled family samples keep their labels
    assert fams["butterfly_router_requests_total"]["samples"][
        ("butterfly_router_requests_total", (("replica", "r3"),))] == 3.0
    # garbage lines are skipped, not fatal
    assert parse_exposition("not a metric line\n# weird\n") == {}


def test_sum_expositions_counters_and_histograms_exact():
    parsed = [parse_exposition(_registry_with(n).render())
              for n in (3, 5)]
    agg = sum_expositions(parsed)
    assert agg["butterfly_requests_total"]["samples"][
        ("butterfly_requests_total", ())] == 8.0
    h = agg["butterfly_ttft_seconds"]["samples"]
    # cumulative bucket sums stay cumulative and +Inf == _count
    assert h[("butterfly_ttft_seconds_bucket", (("le", "0.1"),))] == 2.0
    assert h[("butterfly_ttft_seconds_bucket", (("le", "+Inf"),))] == 4.0
    assert h[("butterfly_ttft_seconds_count", ())] == 4.0
    # gauges never aggregate by summation
    assert "butterfly_queue_depth" not in agg
    # distinct label children survive as distinct series
    fam = agg["butterfly_router_requests_total"]["samples"]
    assert fam[("butterfly_router_requests_total",
                (("replica", "r3"),))] == 3.0
    assert fam[("butterfly_router_requests_total",
                (("replica", "r5"),))] == 5.0


def test_sum_expositions_drops_mismatched_ladders():
    a = parse_exposition(_registry_with(1, ladder=(0.1, 1.0)).render())
    b = parse_exposition(_registry_with(1, ladder=(0.2, 2.0)).render())
    agg = sum_expositions([a, b])
    # a partial bucket sum would render +Inf != _count: drop the family
    assert "butterfly_ttft_seconds" not in agg
    assert agg["butterfly_requests_total"]["samples"][
        ("butterfly_requests_total", ())] == 2.0


def test_render_parsed_renames_namespaced():
    agg = sum_expositions(
        [parse_exposition(_registry_with(2).render())])
    text = "\n".join(render_parsed(
        agg, rename=lambda n: n.replace("butterfly_",
                                        "butterfly_fleet_", 1)))
    assert "butterfly_fleet_requests_total 2" in text
    assert 'butterfly_fleet_ttft_seconds_bucket{le="+Inf"} 2' in text
    assert 'butterfly_fleet_router_requests_total{replica="r2"} 2' in text
    # every sample line is still a legal prometheus series
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        assert re.match(r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$",
                        line), line


# -- fleet trace merging ------------------------------------------------------

def test_tracer_request_id_filter_and_lookup():
    tr = Tracer()
    tr.begin_request(0, request_id="a")
    tr.begin_request(1, request_id="b")
    tr.begin_request(2, request_id="a")  # retry of the same client id
    assert [t["id"] for t in tr.timelines(request_id="a")] == [0, 2]
    assert tr.find_by_request_id("a")["id"] == 2  # newest wins
    assert tr.find_by_request_id("zzz") is None
    dump = tr.dump(request_id="b", n_global=0)
    assert [t["id"] for t in dump["requests"]] == [1]
    assert dump["global_events"] == []


def _fleet_tracers():
    """A synthetic control plane + one replica tracing the same id.
    Leg events are recorded at leg END carrying dur_s, like the real
    FleetHandler — the sleeps make the ends (and therefore the derived
    start_wall ordering) physically real."""
    import time
    cp = Tracer()
    cp.begin_request(0, request_id="rq", path="/generate")
    time.sleep(0.002)
    cp.event(0, "classify", dur_s=0.001, decision="disagg")
    rep = Tracer()
    rep.begin_request(7, request_id="rq")
    rep.event(7, "first_token", ttft_s=0.002)
    rep.event(7, "finish", state="finished", tokens=1)
    time.sleep(0.012)
    cp.event(0, "prefill_leg", dur_s=0.01, replica="a:1", status="ok")
    time.sleep(0.021)
    cp.event(0, "decode_leg", dur_s=0.02, replica="b:1", status="ok")
    cp.event(0, "finish", state="disaggregated", tokens=8, total_s=0.033,
             ttft_s=0.012, slo_ttft_ok=True)
    return cp, rep


def test_merge_fleet_trace_common_clock_and_offset():
    cp, rep = _fleet_tracers()
    control = {"timeline": cp.timeline(0), "t0_wall": cp.t0_wall,
               "t0_monotonic": cp.t0_monotonic}
    merged = merge_fleet_trace("rq", control, {
        "a:1": {"dump": rep.dump(request_id="rq"), "offset_s": 0.25}})
    # every event lands on one clock, time-sorted
    ts = [ev["t_wall"] for ev in merged["merged"]]
    assert ts == sorted(ts)
    assert {ev["source"] for ev in merged["merged"]} == {"control", "a:1"}
    # the replica's events shifted EARLIER by its +250ms clock offset
    zero = merge_fleet_trace("rq", control, {
        "a:1": {"dump": rep.dump(request_id="rq"), "offset_s": 0.0}})
    t_off = [e["t_wall"] for e in merged["merged"]
             if e["source"] == "a:1"]
    t_zero = [e["t_wall"] for e in zero["merged"]
              if e["source"] == "a:1"]
    assert all(abs((z - o) - 0.25) < 1e-9
               for z, o in zip(t_zero, t_off))
    # legs come from the control-plane dur_s spans, waterfall-ordered
    assert [leg["name"] for leg in merged["legs"]] == \
        ["classify", "prefill_leg", "decode_leg"]
    assert merged["legs_total_s"] == pytest.approx(0.031)
    assert merged["total_s"] == pytest.approx(0.033)
    assert merged["slo"]["slo_ttft_ok"] is True
    json.dumps(merged)  # the /fleet/trace body must be JSON-ready


def test_merge_fleet_trace_missing_replica_degrades():
    cp, _ = _fleet_tracers()
    control = {"timeline": cp.timeline(0), "t0_wall": cp.t0_wall,
               "t0_monotonic": cp.t0_monotonic}
    merged = merge_fleet_trace("rq", control, {
        "a:1": {"dump": None, "offset_s": None, "error": "refused"},
        "b:1": {"dump": {"requests": [], "t0_wall": 0.0,
                         "t0_monotonic": 0.0}, "offset_s": 0.0}})
    # control-plane spans survive alone; both replicas marked missing
    assert {ev["source"] for ev in merged["merged"]} == {"control"}
    assert merged["sources"]["a:1"]["missing"] is True
    assert merged["sources"]["a:1"]["error"] == "refused"
    assert merged["sources"]["b:1"]["missing"] is True
    assert len(merged["legs"]) == 3


# -- tools/trace_report.py smoke --------------------------------------------

def _synthetic_dump(path):
    tr = Tracer()
    for rid in range(3):
        tr.begin_request(rid, request_id=f"client-{rid}", prompt_len=8)
        tr.event(rid, "admit", slot=rid % 2, queue_wait_s=0.001)
        tr.event(rid, "prefill_chunk", start=0, tokens=8)
        tr.event(rid, "prefill_done", tokens=8)
        tr.event(rid, "first_token", ttft_s=0.01)
        if rid == 1:
            tr.event(rid, "preempt", slot=1, preemptions=1)
            tr.event(rid, "admit", slot=0, resumed=True)
        tr.event(rid, "finish", state="finished", tokens=4)
    for i in range(5):
        tr.event(None, "engine.table_sync")
    tr.dump_json(str(path))
    return path


def test_trace_report_summary_and_timeline(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "tools" / "trace_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    dump = _synthetic_dump(tmp_path / "trace.json")
    rows = mod.summary_rows(mod.load_dump(str(dump)))
    assert len(rows) == 3
    assert rows[1]["preemptions"] == 1
    text = mod.render_summary(mod.load_dump(str(dump)))
    assert "client-0" in text and "3 request(s)" in text
    assert "5 global event(s)" in text and "tick(s)" not in text
    # the tick count comes from a /debug/ticks body
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    for _ in range(5):
        log.record(0.01, {"mixed": 0.01}, batch=2, generated=2)
    text = mod.render_summary(mod.load_dump(str(dump)), log.dump())
    assert "5 tick(s), 10 token(s) generated" in text
    tl = mod.render_timeline(mod.load_dump(str(dump)), 1)
    assert "preempt" in tl and "request_id=client-1" in tl
    with pytest.raises(ValueError):
        mod.render_timeline(mod.load_dump(str(dump)), 99)
    # a non-dump JSON file is a loud error, not a silent empty report
    bad = tmp_path / "bad.json"
    bad.write_text("[1,2,3]")
    with pytest.raises(ValueError):
        mod.load_dump(str(bad))


def test_trace_report_cli_smoke(tmp_path):
    """The CLI entrypoint can't rot: run it as a real subprocess on a
    synthetic dump (stdlib-only import path — no jax startup cost)."""
    dump = _synthetic_dump(tmp_path / "trace.json")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         str(dump)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "3 request(s)" in out.stdout
    out2 = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         str(dump), "--json"],
        capture_output=True, text=True, timeout=60)
    assert out2.returncode == 0, out2.stderr
    assert len(json.loads(out2.stdout)) == 3
    # missing file exits 2 with a diagnostic on stderr
    out3 = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=60)
    assert out3.returncode == 2 and "error:" in out3.stderr


def test_trace_report_fleet_cli_smoke(tmp_path):
    """--fleet renders a dumped merged trace (the GET /fleet/trace
    body) as a real subprocess — stdlib-only, no jax import — so
    report-rendering regressions fail tier-1."""
    cp, rep = _fleet_tracers()
    merged = merge_fleet_trace(
        "rq", {"timeline": cp.timeline(0), "t0_wall": cp.t0_wall,
               "t0_monotonic": cp.t0_monotonic},
        {"a:1": {"dump": rep.dump(request_id="rq"), "offset_s": 0.0},
         "b:1": {"dump": None, "offset_s": None, "error": "refused"}})
    path = tmp_path / "fleet.json"
    path.write_text(json.dumps(merged))
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         "--fleet", str(path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for needle in ("request_id=rq", "prefill_leg", "decode_leg",
                   "legs sum", "MISSING", "slo:"):
        assert needle in out.stdout, (needle, out.stdout)
    # a per-request dump is not a fleet dump: loud error, exit 2
    plain = tmp_path / "plain.json"
    _synthetic_dump(plain)
    out2 = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         "--fleet", str(plain)],
        capture_output=True, text=True, timeout=60)
    assert out2.returncode == 2 and "merged" in out2.stderr


# -- tick anatomy: the timeline ring (ISSUE 15) -----------------------------

def _tick_entry(log, wall=0.01, **kw):
    phases = kw.pop("phases", {"admit": 0.002, "dispatch": 0.004,
                               "drain_oldest": 0.003, "other": 0.001})
    log.record(wall, phases, **kw)


def test_ticklog_bounded_and_seq_monotonic():
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog(capacity=4)
    for i in range(10):
        _tick_entry(log, wall=0.01 * (i + 1), batch=i)
    d = log.dump()
    assert len(d["ticks"]) == 4 and d["next_seq"] == 10
    seqs = [t["seq"] for t in d["ticks"]]
    assert seqs == sorted(seqs) == [6, 7, 8, 9]
    # ?n=K limit semantics (the /debug/ticks query)
    assert [t["seq"] for t in log.dump(n=2)["ticks"]] == [8, 9]
    assert log.dump(n=0)["ticks"] == []
    json.dumps(d)  # the /debug/ticks body must be JSON-ready


def test_ticklog_record_copies_phases():
    """The ring entry must not alias the scheduler's reusable phase
    accumulator — zeroing it for the next tick would rewrite history."""
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    phases = {"admit": 0.5}
    log.record(0.5, phases)
    phases["admit"] = 0.0
    assert log.dump()["ticks"][0]["phases"]["admit"] == 0.5


def test_ticklog_phase_percentiles_and_combined_drain():
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    for i in range(20):
        log.record(0.01, {"admit": 0.001 * i, "drain_oldest": 0.002,
                          "drain_barrier": 0.003})
    pp = log.phase_percentiles()
    assert pp["drain"]["p50"] == pytest.approx(0.005)
    assert pp["admit"]["p95"] >= pp["admit"]["p50"]
    assert TickLog().phase_percentiles() == {}


def test_ticklog_record_carries_the_starvation_clock():
    """The record's fields of the starvation clock and their nulls: a
    tick that launched nothing has no `starved_s`, one that launched onto
    a busy device has 0.0 and no cause, and the table is a copy."""
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    log.record(0.01, {"mixed": 0.01})                       # launched nothing
    by = {"drain.flush_count": 0.002, "admit.seed": 0.001}
    log.record(0.02, {"mixed": 0.02}, program="bf_mixed_block_win",
               starved_s=0.003, starved_cause="finish", starved_by=by,
               gap_s=0.0004, profiled=True)
    log.record(0.02, {"mixed": 0.02}, program="bf_decode_block_win",
               starved_s=0.0, starved_by={})                # a busy device
    by["admit.seed"] = 9.0
    idle, starved, busy = log.dump()["ticks"]
    assert idle["starved_s"] is None and idle["starved_cause"] is None
    assert idle["starved_by"] == {} and idle["gap_s"] == 0.0
    assert idle["profiled"] is False
    assert starved["starved_s"] == 0.003 and starved["starved_cause"] == "finish"
    assert starved["starved_by"] == {"drain.flush_count": 0.002,
                                     "admit.seed": 0.001}
    assert starved["gap_s"] == 0.0004 and starved["profiled"] is True
    assert busy["starved_s"] == 0.0 and busy["starved_cause"] is None
    assert busy["starved_by"] == {}
    json.dumps(log.dump())


@pytest.mark.parametrize("kw, rows, steps", [
    (dict(), None, None),                       # a model without, or no block
    (dict(latent_load=[4 * 6 * 3100.0, 4]), 74400.0, 4),
    (dict(latent_load=[0.0, 8]), 0.0, 8),       # blocks of chunks alone
])
def test_ticklog_record_carries_the_latent_rows(kw, rows, steps):
    """A latent-attention model's tick records: the cached rows the
    drained blocks' decode rows read and the steps those blocks ran, as
    sums; null for every other model and beside the other families'
    counters, which stay null."""
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    log.record(0.02, {"mixed": 0.02}, program="bf_mixed_block_win",
               expert_load=[244.0, 9.0, 4.0], **kw)
    tick, = log.dump()["ticks"]
    assert (tick["latent_rows"], tick["latent_steps"]) == (rows, steps)
    assert tick["experts_touched"] == 244.0
    assert tick["ssm_rows"] is None and tick["kv_rows_live"] is None
    json.dumps(tick)


def test_the_latent_rows_gauge_is_registered_with_its_help():
    """`butterfly_latent_rows_read` in the scheduler's registry: a gauge
    every model's scheduler holds (0 without latent attention), named in
    the exposition with its help text."""
    import jax
    from butterfly_tpu.core.config import RuntimeConfig, tiny
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("llama", dtype="float32")
    sched = Scheduler(ServingEngine(
        Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)),
        RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4)))
    assert sched.registry.snapshot()["latent_rows_read"] == 0
    text = sched.registry.render()
    assert "# TYPE butterfly_latent_rows_read gauge" in text
    assert "latent rows" in text


@pytest.mark.parametrize("kw, rows, steps", [
    (dict(), None, None),                       # one stream, or no block
    (dict(hc_load=[4 * 128.0 + 32, 4]), 544.0, 4),
    (dict(hc_load=[64.0, 8], latent_load=[0.0, 8]), 64.0, 8),
])
def test_ticklog_record_carries_the_positions_mixed(kw, rows, steps):
    """The tick records of a model of n residual streams (hc_mult): the
    positions whose streams the drained blocks' steps mixed and the
    steps those blocks ran, as sums; null for every other model, beside
    the other families' counters."""
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    log.record(0.02, {"mixed": 0.02}, program="bf_mixed_block_win",
               expert_load=[64.0, 12.0, 8.0], **kw)
    tick, = log.dump()["ticks"]
    assert (tick["hc_rows"], tick["hc_steps"]) == (rows, steps)
    assert tick["experts_touched"] == 64.0 and tick["ssm_rows"] is None
    json.dumps(tick)


def test_the_positions_mixed_counter_is_registered_with_its_help():
    """`butterfly_hc_rows_mixed_total` in the scheduler's registry: a
    counter every model's scheduler holds (0 for a model of one stream),
    named in the exposition with its help text."""
    import jax
    from butterfly_tpu.core.config import RuntimeConfig, tiny
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("llama", dtype="float32")
    sched = Scheduler(ServingEngine(
        Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)),
        RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4)))
    assert sched.registry.snapshot()["hc_rows_mixed_total"] == 0
    text = sched.registry.render()
    assert "# TYPE butterfly_hc_rows_mixed_total counter" in text
    assert "residual streams" in text


def test_trace_report_prints_the_starved_seconds(tmp_path):
    """`trace_report.py --ticks` reads a /debug/ticks dump without the
    benchmark: the starved seconds as a share of what the ticks span, by
    cause and by span, most first; nothing for an older program's dump."""
    import importlib.util
    from butterfly_tpu.obs.ticklog import TickLog
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "tools" / "trace_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    log = TickLog()
    log.record(0.10, {}, generated=4, starved_s=0.0, starved_by={},
               gap_s=0.01)
    log.record(0.10, {}, generated=4, starved_s=0.030, starved_cause="finish",
               starved_by={"drain.flush_count": 0.020, "admit.seed": 0.010},
               gap_s=0.01, profiled=True, barrier_causes=["finish"])
    log.record(0.10, {}, generated=4, starved_s=0.010, starved_cause="exposed",
               starved_by={"drain.emit": 0.004, "admit.seed": 0.006},
               gap_s=0.07, finishes_inline=2)
    log.record(0.10, {}, generated=0, gap_s=0.01,            # launched nothing
               barrier_causes=["cancel", "finish"], finishes_inline=1)
    # the barriers by cause, most first, beside the finishes a lazy
    # drain took without one
    barriers = mod.barrier_lines(log.dump()["ticks"])
    assert barriers == ["full barriers: finish 2  cancel 1; finishes at a "
                        "lazy drain, no barrier: 3"]
    lines = mod.starved_lines(log.dump()["ticks"])
    assert lines == [
        "device starved 40.0ms of 500.0ms (8.0%) in 2 of 4 tick(s), "
        "1 under a capture",
        "  by cause: finish 30.0ms  exposed 10.0ms",
        "  by span: drain.flush_count 20.0ms  admit.seed 16.0ms  "
        "drain.emit 4.0ms"]
    dump = _synthetic_dump(tmp_path / "trace.json")
    text = mod.render_summary(mod.load_dump(str(dump)), log.dump())
    assert "4 tick(s), 12 token(s) generated" in text
    assert text.endswith("\n".join(barriers + lines))
    # a dump of a program older than the clock: the counts and no more
    old = {"ticks": [{k: v for k, v in t.items()
                      if not k.startswith("starved")
                      and k not in ("gap_s", "finishes_inline")}
                     for t in log.dump()["ticks"]]}
    assert mod.starved_lines(old["ticks"]) == []
    assert mod.barrier_lines(old["ticks"]) == []
    assert mod.render_summary(mod.load_dump(str(dump)), old).endswith(
        "4 tick(s), 12 token(s) generated")
    # and the CLI prints it
    ticks = tmp_path / "ticks.json"
    ticks.write_text(json.dumps(log.dump()))
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"), str(dump),
         "--ticks", str(ticks)], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "by cause: finish 30.0ms" in out.stdout


@pytest.mark.parametrize("tool", ["tick_report", "trace_report"])
def test_both_reports_print_the_cpu_clock_and_a_stalls_account(tmp_path, tool):
    """`tick_report.py` and `trace_report.py --ticks` read the CPU clock
    of a /debug/ticks dump: the tick thread's CPU seconds, where it was
    off a CPU by span (the device waits marked), what the process did
    meanwhile and the account of every stalled tick; nothing for the
    records of a program older than the clock."""
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    for _ in range(3):
        log.record(0.10, {"other": 0.10}, fetch_s=0.06, cpu_s=0.03,
                   off_cpu_by={"drain.fetch": 0.06, "drain.emit": 0.01},
                   proc_cpu_s=0.05, gc_s=0.002, gc_collections=1,
                   gc_generation=0, run_delay_s=0.001)
    stall = {"phase": "mixed", "span": "dispatch.launch",
             "cause": "blocked", "excess_s": 3.75}
    log.record(3.85, {"mixed": 3.85}, fetch_s=0.03, cpu_s=0.04,
               off_cpu_by={"drain.fetch": 0.03, "dispatch.launch": 3.78},
               proc_cpu_s=0.10, run_delay_s=0.0, stall=stall)
    # one that read the CPU clock at its two ends alone: no table
    log.record(0.10, {"other": 0.10}, fetch_s=0.06, cpu_s=0.02,
               run_delay_s=0.0)
    log.record(0.10, {"other": 0.10})          # an older caller: no clock
    ticks = tmp_path / "ticks.json"
    ticks.write_text(json.dumps(log.dump()))
    if tool == "tick_report":
        cmd = [str(REPO / "tools" / "tick_report.py"), str(ticks)]
    else:
        cmd = [str(REPO / "tools" / "trace_report.py"),
               str(_synthetic_dump(tmp_path / "trace.json")),
               "--ticks", str(ticks)]
    out = subprocess.run([sys.executable, *cmd], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    text = out.stdout
    # five clocked ticks of 4.25 s, 0.15 s of them on a CPU; the four that
    # carry the table span 4.15 s: 0.21 s in the device waits, 3.81 s off
    # a CPU elsewhere
    assert "tick thread on a CPU 0.1500s (3.5% of tick wall, 30.000 ms a " \
        "tick)" in text
    assert "in the 4 of 5 tick(s) that clocked every span (4.1500s wall): " \
        "other threads' CPU 0.1200s; off a CPU 0.2100s (5.1%) waiting for " \
        "the device, 3.8100s (91.8%) elsewhere" in text
    assert "meanwhile: 3 collection(s) 0.0060s, runnable with no CPU " \
        "0.0030s" in text
    spans = text[text.index("off-CPU seconds by span:"):]
    assert spans.index("dispatch.launch") < spans.index("drain.fetch") \
        < spans.index("drain.emit")
    assert "(device wait)" in spans.splitlines()[2]
    assert "stalled ticks: 1" in text
    assert "tick 3: blocked in mixed / dispatch.launch, 3.750s over the " \
        "usual; wall_s=3.85 fetch_s=0.03 cpu_s=0.04 proc_cpu_s=0.1" in text
    # a dump of a program older than the clock: not a line of it
    old = {"ticks": [{k: v for k, v in t.items()
                      if k not in ("cpu_s", "off_cpu_by", "stall")}
                     for t in log.dump()["ticks"]]}
    ticks.write_text(json.dumps(old))
    out = subprocess.run([sys.executable, *cmd], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "tick thread" not in out.stdout and "stalled" not in out.stdout
    # the bare list of records, as the benchmark keeps it in `ticks.json`
    ticks.write_text(json.dumps(log.dump()["ticks"]))
    out = subprocess.run([sys.executable, *cmd], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "stalled ticks: 1" in out.stdout
    if tool == "tick_report":
        import importlib.util
        spec = importlib.util.spec_from_file_location("tick_report", cmd[0])
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        line = mod.tick_line(log.dump()["ticks"][3])
        assert "cpu=0.0400s STALL=blocked@dispatch.launch " in line
        assert "cpu=" not in mod.tick_line(log.dump()["ticks"][5])
        assert mod.phase_stats(old)["cpu"] == {}


# -- anomaly flight recorder (ISSUE 15) -------------------------------------

def _validate_artifact(art):
    from butterfly_tpu.obs.ticklog import FLIGHTREC_SCHEMA
    assert art["schema"] == FLIGHTREC_SCHEMA
    for key in ("reason", "seed", "t_wall", "next_seq", "signals",
                "event_counts", "events"):
        assert key in art, key
    json.dumps(art)


def test_flight_recorder_ring_bounded():
    from butterfly_tpu.obs.ticklog import FlightRecorder
    fr = FlightRecorder(capacity=3)
    for i in range(7):
        fr.note("admit", id=i)
    d = fr.dump()
    assert d["enabled"] and len(d["events"]) == 3
    assert [e["id"] for e in d["events"]] == [4, 5, 6]
    seqs = [e["seq"] for e in d["events"]]
    assert seqs == sorted(seqs)
    json.dumps(d)


def test_flight_recorder_slo_burn_trigger():
    """Poll at burn >= threshold MUST dump (a threshold weakened to
    inf would silently never fire)."""
    from butterfly_tpu.obs.ticklog import FlightRecorder
    fr = FlightRecorder(slo_burn_threshold=0.5)
    fr.note("admit", id=0)
    assert fr.poll({"slo_burn_rate": 0.4}) is None
    art = fr.poll({"slo_burn_rate": 0.6})
    assert art is not None and art["reason"] == "slo_burn"
    _validate_artifact(art)
    assert art["signals"]["slo_burn_rate"] == 0.6
    assert art["event_counts"] == {"admit": 1}
    assert fr.dump()["triggers_fired"] == {"slo_burn": 1}


def test_flight_recorder_burn_zero_never_fires():
    """threshold 0 + burn 0 (no SLO declared anywhere) must stay
    quiet: the recorder never alarms on an idle default setup."""
    from butterfly_tpu.obs.ticklog import FlightRecorder
    fr = FlightRecorder(slo_burn_threshold=0.0)
    assert fr.poll({"slo_burn_rate": 0.0}) is None


def test_flight_recorder_preempt_storm_and_cooldown():
    from butterfly_tpu.obs.ticklog import FlightRecorder
    fr = FlightRecorder(preempt_storm=3, cooldown_s=3600.0)
    assert fr.poll({"preemptions_total": 0}) is None
    assert fr.poll({"preemptions_total": 2}) is None
    art = fr.poll({"preemptions_total": 3})
    assert art is not None and art["reason"] == "preempt_storm"
    _validate_artifact(art)
    # cooldown: the signal staying bad must not spam artifacts
    assert fr.poll({"preemptions_total": 9}) is None
    assert len(fr.dump()["dumps"]) == 1


def test_flight_recorder_expiry_burst_trigger():
    from butterfly_tpu.obs.ticklog import FlightRecorder
    fr = FlightRecorder(expiry_burst=2)
    assert fr.poll({"deadline_expired_total": 0}) is None
    art = fr.poll({"deadline_expired_total": 2})
    assert art is not None and art["reason"] == "expiry_burst"


def test_flight_recorder_wedge_trigger_and_dump_dir(tmp_path):
    """The wedge latch calls trigger() directly (the tick loop may be
    dead); with dump_dir set the artifact lands on disk as JSON."""
    from butterfly_tpu.obs.ticklog import FlightRecorder
    fr = FlightRecorder(dump_dir=str(tmp_path / "rec"))
    fr.note("wedge", error="heartbeat failed")
    art = fr.trigger("wedge", {"error": "heartbeat failed"})
    _validate_artifact(art)
    assert "path" in art
    on_disk = json.loads(Path(art["path"]).read_text())
    assert on_disk["reason"] == "wedge"
    assert on_disk["events"][0]["kind"] == "wedge"


# -- tools/tick_report.py smoke ---------------------------------------------

def _synthetic_ticks(path, n=12):
    from butterfly_tpu.obs.ticklog import TickLog
    log = TickLog()
    for i in range(n):
        phases = {"expire": 0.0001, "drain_oldest": 0.001,
                  "drain_barrier": 0.002 if i % 3 == 0 else 0.0,
                  "admit": 0.003, "assemble": 0.0005,
                  "dispatch": 0.004, "mixed": 0.005, "spec_emit": 0.0,
                  "flush": 0.0002, "other": 0.0008}
        wall = sum(phases.values())
        log.record(wall, phases, fetch_s=0.0015, inflight=2,
                   barrier_causes=["admission"] if i % 3 == 0 else [],
                   finishes_inline=i % 2, batch=4, waiting=i % 2, pages_free=10, generated=8)
    path.write_text(json.dumps({"enabled": True, **log.dump()}))
    return path


def test_tick_report_stats_and_reconciliation(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tick_report", REPO / "tools" / "tick_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    dump = mod.load_dump(str(_synthetic_ticks(tmp_path / "ticks.json")))
    s = mod.phase_stats(dump)
    assert s["ticks"] == 12
    # THE acceptance property: phase sums reconcile with tick wall
    assert abs(s["reconciliation"] - 1.0) <= 0.10
    assert s["host_frac"] + s["device_frac"] == pytest.approx(1.0)
    # top-terms order: totals descending, dispatch ahead of expire
    totals = [p["total_s"] for p in s["phases"]]
    assert totals == sorted(totals, reverse=True)
    assert s["barrier_causes"] == {"admission": 4}
    assert s["finishes_inline"] == 6
    text = mod.render(dump)
    assert "dispatch" in text and "barriers by cause" in text
    assert "finishes taken at a lazy drain, no barrier: 6" in text
    assert mod.tick_line(dump["ticks"][1]).endswith(
        "barriers=- finishes_inline=1")
    # the top-terms table speaks the mixed-dispatch vocabulary: the
    # fused phase renders with its glossary note
    assert "mixed" in text and "ONE fused dispatch" in text
    # a non-dump file is a loud error
    bad = tmp_path / "bad.json"
    bad.write_text("[1,2]")
    with pytest.raises(ValueError):
        mod.load_dump(str(bad))


def test_tick_report_cli_smoke(tmp_path):
    """Subprocess smoke (stdlib-only import path, like trace_report)."""
    dump = _synthetic_ticks(tmp_path / "ticks.json")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tick_report.py"),
         str(dump)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "12 tick(s)" in out.stdout
    assert "phase sums account for" in out.stdout
    out2 = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tick_report.py"),
         str(dump), "--json"],
        capture_output=True, text=True, timeout=60)
    assert out2.returncode == 0, out2.stderr
    stats = json.loads(out2.stdout)
    assert abs(stats["reconciliation"] - 1.0) <= 0.10
    out3 = subprocess.run(
        [sys.executable, str(REPO / "tools" / "tick_report.py"),
         str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=60)
    assert out3.returncode == 2 and "error:" in out3.stderr
