"""Fleet control plane (ISSUE 6): disaggregated prefill/decode with
cross-replica KV page transfer.

Layered like the subsystem:

* allocator units — the transfer surface on PrefixCachingAllocator
  (lookup / pin / unpin / import_page) with the full-accounting
  invariant checked after every mutation;
* kvtransfer units — export/import payload roundtrip between two real
  schedulers, geometry refusal, missing-hash reporting;
* HTTP endpoints — /kv/pages, /kv/import, the enriched /health;
* the control plane — classification, the disaggregated handoff with
  BYTE-IDENTICAL greedy parity vs single-replica serving (the
  acceptance contract), fallback when a tier dies mid-handoff;
* the fleet soak — 2 prefill + 2 decode replicas through a rolling
  drain/restart cycle with zero dropped un-started requests and a
  positive transfer hit rate;
* the observability plane (ISSUE 7) — /fleet/trace merged waterfalls
  (leg ordering, common clock, missing-replica degradation),
  /fleet/metrics rollup sums vs per-replica /metrics, and SLO
  attainment through the soak.

Everything runs in-process on the tiny model (the test_router.py
idiom); the multi-replica pieces are slow-marked in conftest.py.
"""
import json
import urllib.error
import urllib.request

import jax
import pytest

from butterfly_tpu.cache.prefix import (
    PrefixCachingAllocator, chain_block_hashes)
from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.fleet.kvtransfer import export_payload, import_payload
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler

CFG = tiny("llama", dtype="float32", param_dtype="float32")
PAGE = 8


@pytest.fixture(scope="module")
def shared_model():
    model = Model(CFG)
    return model, model.init(jax.random.PRNGKey(0))


def make_sched(shared_model, max_batch=2, max_seq=128, num_pages=None):
    model, params = shared_model
    rt = RuntimeConfig(max_batch_size=max_batch, max_seq_len=max_seq,
                       page_size=PAGE, num_pages=num_pages,
                       prefix_caching=True)
    return Scheduler(ServingEngine(model, params, rt))


def post(url, path, obj, timeout=60):
    req = urllib.request.Request(
        url + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def get(url, path, timeout=30):
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------------------
# allocator units: the transfer surface
# ---------------------------------------------------------------------------

def test_lookup_and_import_page():
    a = PrefixCachingAllocator(num_pages=8, page_size=4, max_pages_per_seq=8)
    seq = list(range(9))  # 2 full pages
    a.admit(0, seq, len(seq) + 1)
    a.register(0, seq)
    h1, h2 = chain_block_hashes(seq, 4)
    assert a.lookup(h1) == a.pages_of(0)[0]
    assert a.lookup(h2) == a.pages_of(0)[1]
    assert a.lookup(b"\x00" * 32) is None
    # import of an already-registered digest is a no-op (idempotent)
    assert a.import_page(h1) is None
    # a fresh digest claims a page and registers it warm (evictable)
    h3 = chain_block_hashes(seq[:4] + [99] * 4, 4)[-1]
    pid = a.import_page(h3)
    assert pid is not None and a.lookup(h3) == pid
    assert pid in a._evictable
    a.check_invariants()
    a.release(0)
    a.check_invariants()


def test_imported_pages_attach_like_local_hits():
    """A chain imported (not computed locally) must satisfy a later
    admit exactly like a locally registered prefix."""
    a = PrefixCachingAllocator(num_pages=8, page_size=4, max_pages_per_seq=8)
    seq = list(range(10))  # 2 full pages + tail
    for h in chain_block_hashes(seq, 4):
        assert a.import_page(h) is not None
    a.check_invariants()
    assert a.admit(0, seq, len(seq) + 1) == 8  # both pages hit
    a.check_invariants()


def test_pin_blocks_eviction():
    """A pinned warm page must survive allocation pressure that would
    otherwise evict it (the export-in-progress guarantee)."""
    a = PrefixCachingAllocator(num_pages=2, page_size=4, max_pages_per_seq=2)
    seq = list(range(5))  # 1 full page
    a.admit(0, seq, len(seq) + 1)     # 2 pages: 1 registered + 1 private
    a.register(0, seq)
    (h,) = chain_block_hashes(seq, 4)
    pid = a.lookup(h)
    a.release(0)                       # registered page goes warm
    a.pin([pid])
    # both raw-free pages get consumed; the pinned page must NOT be
    # recycled even though the free list runs dry
    assert a.grow(1, 4) is not None
    assert a.grow(1, 8) is None        # only the pinned page "left"
    assert a.lookup(h) == pid          # still registered
    a.unpin([pid])
    assert a.grow(1, 8) is not None    # now evictable again
    assert a.lookup(h) is None         # eviction deregistered it
    a.check_invariants()


def test_import_page_exhaustion():
    a = PrefixCachingAllocator(num_pages=1, page_size=4, max_pages_per_seq=4)
    a.grow(0, 4)  # the only page is slot-held: not free, not evictable
    with pytest.raises(MemoryError):
        a.import_page(b"\x01" * 32)
    a.check_invariants()


# ---------------------------------------------------------------------------
# kvtransfer payloads between two real schedulers
# ---------------------------------------------------------------------------

def test_export_import_roundtrip_and_warm_hit(shared_model):
    """Pages exported from A and imported into B give B's admission a
    full prefix hit, and the decoded continuation is byte-identical to
    a single-replica run — K/V bytes moved, semantics did not."""
    prompt = list(range(1, 41))  # 5 full pages
    a = make_sched(shared_model)
    ra = a.submit(prompt, max_new_tokens=1, stop_token=-1)
    a.run_until_done()
    hashes = [h.hex() for h in chain_block_hashes(prompt, PAGE)]
    payload = export_payload(a, hashes)
    assert [p["hash"] for p in payload["pages"]] == hashes
    assert payload["missing"] == []
    assert payload["bytes"] > 0

    b = make_sched(shared_model)
    res = import_payload(b, payload)
    assert res["imported"] == len(hashes) and not res["no_space"]
    # B continues from A's first token with a full-prefix cache hit
    rb = b.submit(prompt + ra.output, max_new_tokens=7, stop_token=-1)
    b.run_until_done()
    assert b.alloc.hit_tokens == 40  # every full page came from import

    ref = make_sched(shared_model)
    rr = ref.submit(prompt, max_new_tokens=8, stop_token=-1)
    ref.run_until_done()
    assert ra.output + rb.output == rr.output


def test_export_reports_missing_tail(shared_model):
    a = make_sched(shared_model)
    prompt = list(range(1, 25))  # 3 full pages
    a.submit(prompt, max_new_tokens=1, stop_token=-1)
    a.run_until_done()
    other = chain_block_hashes(list(range(50, 90)), PAGE)
    hashes = [h.hex() for h in chain_block_hashes(prompt, PAGE)] \
        + [other[-1].hex()]
    payload = export_payload(a, hashes)
    assert len(payload["pages"]) == 3
    assert payload["missing"] == [other[-1].hex()]
    # a chain that misses at block 0 ships nothing (pages behind a gap
    # are unusable by admit)
    cold = export_payload(a, [other[0].hex()] + hashes)
    assert cold["pages"] == [] and len(cold["missing"]) == 5


def test_import_refuses_geometry_mismatch(shared_model):
    a = make_sched(shared_model)
    prompt = list(range(1, 17))
    a.submit(prompt, max_new_tokens=1, stop_token=-1)
    a.run_until_done()
    payload = export_payload(
        a, [h.hex() for h in chain_block_hashes(prompt, PAGE)])
    bad = dict(payload)
    bad["meta"] = {**payload["meta"], "page_size": 16}
    b = make_sched(shared_model)
    with pytest.raises(ValueError, match="geometry"):
        import_payload(b, bad)
    with pytest.raises(ValueError, match="version"):
        import_payload(b, {**payload, "version": 99})
    # nothing landed
    assert import_payload(b, payload)["imported"] == 2


def test_import_idempotent(shared_model):
    a = make_sched(shared_model)
    prompt = list(range(1, 17))
    a.submit(prompt, max_new_tokens=1, stop_token=-1)
    a.run_until_done()
    payload = export_payload(
        a, [h.hex() for h in chain_block_hashes(prompt, PAGE)])
    b = make_sched(shared_model)
    assert import_payload(b, payload)["imported"] == 2
    again = import_payload(b, payload)
    assert again["imported"] == 0 and again["skipped"] == 2


# ---------------------------------------------------------------------------
# HTTP surface: /health fields, /kv endpoints, /fleet/state
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_1p1d(shared_model):
    from butterfly_tpu.fleet.harness import start_fleet
    model, params = shared_model
    # generous CPU-smoke objectives: the SLO layer records attainment
    # (fleet_slo_* counters, slo_ttft_ok response fields) without ever
    # turning a slow CI box into a flake
    fleet = start_fleet("1p1d", page_size=PAGE, max_batch=2, max_seq=128,
                        disagg_threshold=16, model=model, params=params,
                        slo_ttft_s=120.0, slo_itl_s=120.0)
    yield fleet
    fleet.stop()


def test_health_carries_fleet_signals(fleet_1p1d):
    pre = fleet_1p1d.replicas[0]
    body = get(pre.url, "/health")
    assert body["role"] == "prefill"
    assert body["free_pages"] > 0
    assert body["inflight_depth"] == 0
    assert "queue_depth" in body and "active" in body


def test_kv_endpoint_roundtrip_over_http(fleet_1p1d):
    pre, dec = fleet_1p1d.replicas
    prompt = list(range(1, 25))
    post(pre.url, "/generate", {"tokens": prompt, "max_tokens": 1,
                                "stop_token": -1})
    hashes = ",".join(h.hex() for h in chain_block_hashes(prompt, PAGE))
    payload = get(pre.url, f"/kv/pages?hashes={hashes}")
    assert len(payload["pages"]) == 3 and payload["bytes"] > 0
    res = post(dec.url, "/kv/import", payload)
    assert res["imported"] + res["skipped"] == 3


def test_kv_export_bad_requests(fleet_1p1d):
    pre = fleet_1p1d.replicas[0]
    with pytest.raises(urllib.error.HTTPError) as e:
        get(pre.url, "/kv/pages")
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        get(pre.url, "/kv/pages?hashes=nothex")
    assert e.value.code == 400


def test_kv_import_mismatch_is_409(fleet_1p1d):
    pre, dec = fleet_1p1d.replicas
    prompt = list(range(1, 17))
    post(pre.url, "/generate", {"tokens": prompt, "max_tokens": 1,
                                "stop_token": -1})
    hashes = ",".join(h.hex() for h in chain_block_hashes(prompt, PAGE))
    payload = get(pre.url, f"/kv/pages?hashes={hashes}")
    payload["meta"]["num_layers"] += 1
    with pytest.raises(urllib.error.HTTPError) as e:
        post(dec.url, "/kv/import", payload)
    assert e.value.code == 409


def test_fleet_state_table(fleet_1p1d):
    state = get(fleet_1p1d.url, "/fleet/state")
    assert len(state["replicas"]) == 2
    pre, dec = fleet_1p1d.replicas
    assert state["tiers"]["prefill"] == [pre.rid]
    assert state["tiers"]["decode"] == [dec.rid]
    by_rid = {s["replica"]: s for s in state["replicas"]}
    assert by_rid[pre.rid]["role"] == "prefill"
    assert by_rid[pre.rid]["free_pages"] is not None
    assert "kv_transfer_hit_rate" in state["metrics"]


# ---------------------------------------------------------------------------
# the disaggregated handoff (acceptance: byte-identical greedy parity)
# ---------------------------------------------------------------------------

def test_disaggregated_parity_with_single_replica(fleet_1p1d, shared_model):
    """A request prefilled on replica A and decoded on replica B
    produces byte-identical greedy tokens to single-replica serving,
    with the KV pages actually transferred (B prefix-hits every full
    prompt page instead of recomputing)."""
    pre, dec = fleet_1p1d.replicas
    prompt = list(range(3, 43))  # 5 full pages
    hits_before = dec.sched.alloc.hit_tokens
    r = post(fleet_1p1d.url, "/generate",
             {"tokens": prompt, "max_tokens": 8, "stop_token": -1})
    assert r["disaggregated"] is True
    assert r["prefill_replica"] == pre.rid
    assert r["decode_replica"] == dec.rid
    assert r["kv_pages_imported"] == 5
    assert r["ttft_s"] > 0
    assert dec.sched.alloc.hit_tokens - hits_before == 40

    ref = make_sched(shared_model)
    rr = ref.submit(prompt, max_new_tokens=8, stop_token=-1)
    ref.run_until_done()
    assert r["tokens"] == rr.output


def test_short_prompt_routes_direct(fleet_1p1d):
    before = fleet_1p1d.state.fleet_counters()["direct_requests"]
    r = post(fleet_1p1d.url, "/generate",
             {"tokens": [5, 6, 7], "max_tokens": 2, "stop_token": -1})
    assert "disaggregated" not in r
    after = fleet_1p1d.state.fleet_counters()["direct_requests"]
    assert after == before + 1


def test_string_prompt_routes_direct(fleet_1p1d):
    """String prompts cannot be chain-hashed by the control plane (no
    tokenizer there) — they must dispatch direct, never disaggregate."""
    r = post(fleet_1p1d.url, "/generate",
             {"prompt": "x" * 64, "max_tokens": 2})
    assert "disaggregated" not in r and len(r["tokens"]) == 2


def test_handoff_falls_back_when_prefill_tier_dies(shared_model):
    """Prefill replica dies before the handoff: the control plane falls
    back to a direct dispatch on the decode tier — correct tokens, no
    client-visible failure (the failure-matrix row docs/fleet.md
    documents)."""
    from butterfly_tpu.fleet.harness import start_fleet
    model, params = shared_model
    fleet = start_fleet("1p1d", page_size=PAGE, max_batch=2, max_seq=128,
                        disagg_threshold=16, model=model, params=params)
    try:
        # freeze the prober: the pool must still believe the prefill
        # replica is live, so the request takes the HANDOFF path and
        # exercises the mid-flight fallback (not the planner's
        # dead-replica exclusion)
        fleet.state.pool.stop()
        pre = fleet.replicas[0]
        pre.httpd.shutdown()
        pre.httpd.server_close()
        prompt = list(range(7, 47))
        r = post(fleet.url, "/generate",
                 {"tokens": prompt, "max_tokens": 4, "stop_token": -1,
                  "request_id": "fb-1"})
        assert "disaggregated" not in r and len(r["tokens"]) == 4
        assert fleet.state.fleet_counters()["disagg_fallbacks"] >= 1
        # the dead leg was CLASSIFIED (ISSUE 8 satellite): a refused
        # prefill leg lands in fleet_leg_failures_total{leg,kind},
        # not a bare except bucket
        assert fleet.state.fleet_counters()["leg_failures"] >= 1
        kinds = {k: c.value for k, c in
                 fleet.state._c_leg_fail._children.items()}
        assert kinds.get(("prefill_leg", "refused"), 0) >= 1, kinds
        # and the leg failure fed the replica's circuit breaker
        assert fleet.state.pool.get(pre.rid).breaker_fails >= 1
        ref = make_sched(shared_model)
        rr = ref.submit(prompt, max_new_tokens=4, stop_token=-1)
        ref.run_until_done()
        assert r["tokens"] == rr.output
        # the trace still assembles: the dead prefill replica's leg
        # degrades to control-plane spans only, the fallback event and
        # the direct leg that actually served are both recorded
        tr = get(fleet.url, "/fleet/trace?request_id=fb-1")
        names = [ev["name"] for ev in tr["merged"]
                 if ev["source"] == "control"]
        assert "fallback" in names and "direct_leg" in names
        assert tr["sources"][pre.rid].get("missing") is True
        dec_rid = fleet.replicas[1].rid
        assert tr["sources"][dec_rid]["events"] > 0
    finally:
        fleet.stop()


def test_fleet_deadline_spent_at_arrival_is_504(fleet_1p1d):
    """A request whose deadline budget is already spent 504s at the
    control plane — no classify, no handoff, no replica ever sees it —
    with where/elapsed detail and the fleet counter ticked."""
    before = fleet_1p1d.state.fleet_counters()["deadline_expired"]
    with pytest.raises(urllib.error.HTTPError) as e:
        post(fleet_1p1d.url, "/generate",
             {"tokens": list(range(1, 40)), "max_tokens": 4,
              "stop_token": -1, "deadline_ms": 0,
              "request_id": "dl-arrival-1"})
    assert e.value.code == 504
    body = json.loads(e.value.read())
    assert body["error"] == "deadline exceeded"
    assert body["where"] == "arrival"
    assert body["request_id"] == "dl-arrival-1"
    after = fleet_1p1d.state.fleet_counters()["deadline_expired"]
    assert after == before + 1
    # a generous budget rides the handoff end to end untouched
    r = post(fleet_1p1d.url, "/generate",
             {"tokens": list(range(1, 40)), "max_tokens": 4,
              "stop_token": -1, "deadline_ms": 120_000})
    assert len(r["tokens"]) == 4


def chaos_soak(loadgen):
    """The in-process fleet under the SEEDED stock fault plan
    (fleet/chaos.py default_plan: delayed prefill, 500s and a
    breaker-tripping wedge burst on the decode tier, dropped and
    truncated connections) driven by loadgen, then a burst of requests
    whose deadline is already spent. Returns the counts the soak test
    asserts."""
    from butterfly_tpu.fleet.chaos import default_plan
    from butterfly_tpu.fleet.harness import start_fleet

    plan = default_plan(seed=0)
    max_tokens, disagg_threshold = 8, 16
    shared_len = max(PAGE * 4, disagg_threshold)
    tail = PAGE // 2
    # generous declared objectives: the SLO/shed machinery is ACTIVE
    # (counters live, shed path armed) without turning CPU-smoke
    # latency noise into nondeterministic shedding
    fleet = start_fleet("2p2d", page_size=PAGE, max_batch=2,
                        max_seq=shared_len + tail + max_tokens + 16,
                        disagg_threshold=disagg_threshold,
                        chaos=plan, slo_ttft_s=120.0, slo_itl_s=120.0,
                        warm_len=shared_len + tail)
    try:
        # arm the control plane's flight recorder for the spent-budget
        # burst below: 3 expiries inside the window is a deadline-
        # expiry-burst anomaly at this soak's scale, so the soak also
        # proves the post-mortem path end-to-end (ISSUE 15)
        fleet.state.flightrec.expiry_burst = 3
        # phase 1 — the chaos load: faults fire across both tiers while
        # closed-loop clients demand terminal outcomes
        load = loadgen.run_load(fleet.url, clients=3,
                                requests_per_client=4,
                                prefix_share=0.5, shared_len=shared_len,
                                tail_len=tail, max_tokens=max_tokens,
                                seed=0)
        # phase 2 — a spent-budget burst: every request arrives with a
        # dead deadline and must 504 at the control plane, never
        # touching a queue or a decode slot
        expired = loadgen.run_load(fleet.url, clients=1,
                                   requests_per_client=3,
                                   prefix_share=0.0,
                                   shared_len=shared_len, tail_len=tail,
                                   max_tokens=max_tokens, seed=1,
                                   deadline_ms=0.0)
        # the fleet-wide flight-recorder rollup: control-plane +
        # per-replica rings merged on the probe-offset clock, with the
        # expiry-burst trigger's post-mortem artifact(s) attached
        flightrec = get(fleet.url, "/fleet/flightrecorder")
        deadline_expired = sum(
            r.sched.metrics().get("deadline_expired_total", 0.0)
            for r in fleet.replicas)
        cp = fleet.state.fleet_counters()
        deadline_expired += cp["deadline_expired"]
    finally:
        fleet.stop()
    o1, o2 = load["outcomes"], expired["outcomes"]
    return {
        "requests": load["sent"] + expired["sent"],
        "terminal": load["terminal"] + expired["terminal"],
        "errors": o1["error"] + o2["error"],
        "deadline_504": o1["deadline_504"] + o2["deadline_504"],
        "injected": plan.total_injected,
        "leg_failures": cp["leg_failures"],
        "deadline_expired_total": deadline_expired,
        "flightrec_dumps": len(flightrec.get("dumps", ())),
        "flightrec_reasons": sorted(
            {d.get("reason") for d in flightrec.get("dumps", ())}),
        "flightrec_sources": len(flightrec.get("sources", {})),
        "flightrec_events": len(flightrec.get("events", ())),
    }


def test_chaos_soak_terminal_outcomes(loadgen):
    """The ISSUE 8 acceptance soak: a 2p2d fleet under the SEEDED stock
    fault plan (delays, 500s, a wedge burst, drops, truncations, a
    dropped control-plane leg) driven by loadgen, plus a spent-deadline
    burst. Every submitted request reaches a terminal outcome (tokens,
    429, or 504): zero un-started drops, zero client hangs, zero
    5xx-shaped errors."""
    out = chaos_soak(loadgen)
    assert out["requests"] == 15  # 12 chaos load + 3 expired burst
    assert out["terminal"] == out["requests"]
    assert out["errors"] == 0
    # the faults actually fired (seeded plan, not a quiet pass) and the
    # handoff degraded through its real fallback paths
    assert out["injected"] > 0
    assert out["leg_failures"] > 0
    # the spent-budget burst died at the control plane as terminal 504s
    assert out["deadline_504"] == 3
    assert out["deadline_expired_total"] >= 3
    # flight recorder (ISSUE 15): the spent-deadline burst is a
    # deadline-expiry-burst anomaly at this scale — the control plane's
    # recorder must have produced a post-mortem artifact, and the
    # /fleet/flightrecorder rollup must have merged every source
    # (control plane + all four replicas)
    assert out["flightrec_dumps"] >= 1
    assert "expiry_burst" in out["flightrec_reasons"]
    assert out["flightrec_sources"] == 5  # control + 2p + 2d
    assert out["flightrec_events"] > 0


# ---------------------------------------------------------------------------
# fleet observability: merged traces, metrics rollup, SLO (ISSUE 7)
# ---------------------------------------------------------------------------

def test_fleet_trace_merged_waterfall(fleet_1p1d):
    """The acceptance trace: one disaggregated request yields ONE
    /fleet/trace timeline — control-plane legs (classify → prefill_leg
    → kv transfer → decode_leg) interleaved with BOTH replicas' span
    events on a common clock, leg durations summing to within 10% of
    the measured end-to-end latency, and SLO verdicts attached."""
    pre, dec = fleet_1p1d.replicas
    prompt = list(range(2, 42))  # 5 full pages
    r = post(fleet_1p1d.url, "/generate",
             {"tokens": prompt, "max_tokens": 8, "stop_token": -1,
              "request_id": "trace-e2e-1"})
    assert r["disaggregated"] and r["request_id"] == "trace-e2e-1"
    assert r["slo_ttft_ok"] is True and r["slo_itl_ok"] is True

    tr = get(fleet_1p1d.url, "/fleet/trace?request_id=trace-e2e-1")
    names = [leg["name"] for leg in tr["legs"]]
    assert names == ["classify", "prefill_leg", "kv_export",
                     "kv_import", "decode_leg"]
    # per-leg durations account for the end-to-end latency (10% slack)
    assert tr["total_s"] == pytest.approx(r["total_s"], rel=0.2)
    assert abs(tr["legs_total_s"] - tr["total_s"]) \
        < 0.1 * tr["total_s"]
    # control-plane leg spans are ordered and non-overlapping
    for a, b in zip(tr["legs"], tr["legs"][1:]):
        assert b["start_wall"] >= a["end_wall"] - 1e-4
    # all three processes contribute, merged on one clock
    srcs = {ev["source"] for ev in tr["merged"]}
    assert srcs == {"control", pre.rid, dec.rid}
    ts = [ev["t_wall"] for ev in tr["merged"]]
    assert ts == sorted(ts)
    # within each replica the span events stay in recorded order
    for rid in (pre.rid, dec.rid):
        mine = [ev for ev in tr["merged"] if ev["source"] == rid]
        assert mine and [ev["t_wall"] for ev in mine] == \
            sorted(ev["t_wall"] for ev in mine)
    # the prefill replica's own first_token lands inside the
    # prefill leg's wall-clock span (clock-offset sanity, loopback)
    leg = tr["legs"][1]
    ft = next(ev for ev in tr["merged"]
              if ev["source"] == pre.rid and ev["name"] == "first_token")
    assert leg["start_wall"] - 0.05 <= ft["t_wall"] \
        <= leg["end_wall"] + 0.05
    assert tr["slo"]["slo_ttft_ok"] is True


def test_fleet_trace_direct_request_and_unknown_id(fleet_1p1d):
    """Direct dispatches trace too (classify + direct_leg), and an
    unknown request id is a clean 404, not a 500."""
    post(fleet_1p1d.url, "/generate",
         {"tokens": [5, 6, 7], "max_tokens": 2, "stop_token": -1,
          "request_id": "trace-direct-1"})
    tr = get(fleet_1p1d.url, "/fleet/trace?request_id=trace-direct-1")
    names = [leg["name"] for leg in tr["legs"]]
    assert names[0] == "classify" and "direct_leg" in names
    direct = next(leg for leg in tr["legs"]
                  if leg["name"] == "direct_leg")
    assert direct["replica"] in {r.rid for r in fleet_1p1d.replicas}
    with pytest.raises(urllib.error.HTTPError) as e:
        get(fleet_1p1d.url, "/fleet/trace?request_id=never-seen")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        get(fleet_1p1d.url, "/fleet/trace")
    assert e.value.code == 400


def test_fleet_metrics_rollup_sums_match_replicas(fleet_1p1d):
    """/fleet/metrics counter sums equal the per-replica sums, the
    re-bucketed histograms stay internally consistent (+Inf == _count),
    and the per-replica autoscale gauges are exposed labeled."""
    from butterfly_tpu.obs.registry import parse_exposition
    post(fleet_1p1d.url, "/generate",
         {"tokens": list(range(1, 30)), "max_tokens": 4,
          "stop_token": -1})
    fleet_1p1d.state.pool.probe_all()  # fresh synchronous scrape round
    with urllib.request.urlopen(fleet_1p1d.url + "/fleet/metrics",
                                timeout=30) as resp:
        text = resp.read().decode()
    fams = parse_exposition(text)
    # counters: fleet sum == sum over replicas' own /metrics
    per_replica = 0.0
    for rep in fleet_1p1d.replicas:
        with urllib.request.urlopen(rep.url + "/metrics",
                                    timeout=30) as resp:
            rf = parse_exposition(resp.read().decode())
        per_replica += rf["butterfly_requests_total"]["samples"][
            ("butterfly_requests_total", ())]
    agg = fams["butterfly_fleet_requests_total"]["samples"][
        ("butterfly_fleet_requests_total", ())]
    assert agg == per_replica > 0
    # histograms: re-bucketed exactly, +Inf bucket == _count
    h = fams["butterfly_fleet_ttft_seconds"]["samples"]
    inf = h[("butterfly_fleet_ttft_seconds_bucket", (("le", "+Inf"),))]
    assert inf == h[("butterfly_fleet_ttft_seconds_count", ())] > 0
    # per-replica autoscale gauges, one series per replica
    fp = fams["butterfly_fleet_replica_kv_pages_free"]["samples"]
    assert len(fp) == len(fleet_1p1d.replicas)
    assert fams["butterfly_fleet_replicas_scraped"]["samples"][
        ("butterfly_fleet_replicas_scraped", ())] == 2.0
    # clock offsets learned from the same probe loop (loopback: ~0)
    for snap in fleet_1p1d.state.pool.snapshot():
        assert snap["clock_offset_s"] is not None
        assert abs(snap["clock_offset_s"]) < 5.0


# ---------------------------------------------------------------------------
# load_score page pressure (satellite) — policy-level ordering
# ---------------------------------------------------------------------------

def test_load_score_prefers_page_headroom():
    from butterfly_tpu.router.pool import Replica
    rich = Replica("a:1", "a", 1)
    poor = Replica("b:1", "b", 1)
    rich.free_pages, poor.free_pages = 50, 2
    # equal outstanding/backlog: page headroom breaks the tie
    assert sorted([poor, rich], key=Replica.load_score)[0] is rich
    # outstanding still dominates (freshest signal)
    poor.outstanding, rich.outstanding = 0, 1
    assert sorted([poor, rich], key=Replica.load_score)[0] is poor
    # unknown headroom scores as zero pages (conservative)
    unknown = Replica("c:1", "c", 1)
    unknown.outstanding = 0
    assert sorted([poor, unknown], key=Replica.load_score)[0] is poor


def test_pool_candidates_filter_by_role():
    from butterfly_tpu.router.pool import ReplicaPool
    pool = ReplicaPool(["h:1", "h:2", "h:3"])
    pool.replicas["h:1"].role = "prefill"
    pool.replicas["h:2"].role = "decode"
    pool.replicas["h:3"].role = "both"
    assert {r.rid for r in pool.candidates("prefill")} == {"h:1", "h:3"}
    assert {r.rid for r in pool.candidates("decode")} == {"h:2", "h:3"}
    assert len(pool.candidates()) == 3


# ---------------------------------------------------------------------------
# the fleet soak: rolling drain/restart over 2 prefill + 2 decode
# ---------------------------------------------------------------------------

def test_fleet_soak_rolling_drain_restart(shared_model, loadgen):
    """The acceptance soak: closed-loop load over a 2p2d topology while
    every replica is rolled through drain -> HTTP restart -> undrain.
    Zero dropped un-started requests, transfers actually happened."""
    from butterfly_tpu.fleet.harness import start_fleet
    model, params = shared_model
    fleet = start_fleet("2p2d", page_size=PAGE, max_batch=2, max_seq=128,
                        disagg_threshold=16, model=model, params=params)
    try:
        stats = loadgen.run_fleet_soak(
            fleet.url, clients=3, requests_per_client=3,
            prefix_share=0.5, shared_len=4 * PAGE, tail_len=4,
            max_tokens=4, replicas=fleet.rids,
            restart_hook=lambda rid: fleet.by_rid[rid].restart(),
            slo_ttft_ms=120_000.0, slo_itl_ms=120_000.0)
        assert stats["failed"] == 0, stats["errors"]
        assert stats["ok"] == 9
        assert len(stats["rolling_cycles"]) == 4
        assert all(c["drained"] and c["restarted"]
                   for c in stats["rolling_cycles"])
        fm = stats["fleet_metrics"]
        assert fm["kv_transfer_hit_rate"] > 0
        assert fm["kv_transfer_bytes"] > 0
        assert stats["disaggregated"] > 0
        # client-side SLO attainment against the declared (generous)
        # objectives rides the soak summary
        assert stats["slo_attainment"] == 1.0
        assert stats["slo_ttft_ok"] == stats["ok"]
        # every replica answers again after its restart
        for r in fleet.replicas:
            assert get(r.url, "/health")["status"] == "ok"
        # trace assembly SURVIVED the rolling restarts: every loadgen
        # request id still yields at least its control-plane spans
        # (replica fronts bounced mid-soak; schedulers+tracers live on)
        tr = get(fleet.url, "/fleet/trace?request_id=loadgen-0-0")
        assert any(ev["source"] == "control" for ev in tr["merged"])
        assert [l["name"] for l in tr["legs"]][0] == "classify"
    finally:
        fleet.stop()
