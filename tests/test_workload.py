"""Workload subsystem tests (ISSUE 10).

Three layers:

* pure-host: seeded determinism (same spec + seed => byte-identical
  trace across generate -> save -> load -> save), arrival-process
  statistics (the mutation catalogue's arrival-rate mutant must die
  here), spec round-trips, page-aligned shared prefixes;
* server-level: the canned mixed_chat workload replayed open-loop at a
  tiny in-process server with an under-provisioned page pool provably
  drives serving_preemptions > 0, and SLO-aware admission sheds at
  least one 429 through the PR-8 path — with the loadgen/replay
  summary folding the server-side counters in (client-observed vs
  server-counted in one artifact);
* `butterfly workload generate|replay` CLI smoke.
"""
import json
import statistics
import threading
import urllib.request

import jax
import pytest

from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler
from butterfly_tpu.serve.server import ServerState, make_handler
from butterfly_tpu.utils.tokenizer import ByteTokenizer
from butterfly_tpu.workload.arrivals import (MarkovOnOff, Poisson, Ramp,
                                             assign_arrivals, parse_arrival)
from butterfly_tpu.workload.models import (RequestSpec, Workload,
                                           get_workload, mixed_chat)
from butterfly_tpu.workload.replay import (load_trace, replay_trace,
                                           save_trace, trace_text)

CFG = tiny("llama", dtype="float32", param_dtype="float32")

#: the CPU-smoke mixed_chat shape:
#: decode budgets long enough to keep slots alive across blocks, so a
#: near-instant burst against a tight pool provably contests pages
SMOKE_WL = dict(page_size=8, vocab=258, prompt_lo=8, prompt_hi=48,
                max_new_lo=16, max_new_hi=48)
SMOKE_ARRIVAL = "burst:2000:0.5:0.1"


def smoke_specs(n=12, seed=0):
    wl = mixed_chat(**SMOKE_WL)
    specs = wl.sample(n, seed)
    assign_arrivals(specs, parse_arrival(SMOKE_ARRIVAL), seed)
    return wl, specs


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------


def test_trace_byte_identical_across_generate_save_load():
    """Same workload spec + seed => byte-identical trace text, and a
    loaded trace re-saves byte-identically (generate -> save -> load ->
    save). This is what makes a saved trace a citable benchmark input:
    replaying it twice fires IDENTICAL request sequences."""
    wl1, s1 = smoke_specs()
    wl2, s2 = smoke_specs()
    t1 = trace_text(s1, workload=wl1, arrival=SMOKE_ARRIVAL, seed=0)
    t2 = trace_text(s2, workload=wl2, arrival=SMOKE_ARRIVAL, seed=0)
    assert t1 == t2
    # and the HTTP payloads the replay driver would fire are identical
    assert [s.payload() for s in s1] == [s.payload() for s in s2]


def test_trace_file_roundtrip(tmp_path):
    wl, specs = smoke_specs()
    p1, p2 = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    save_trace(p1, specs, workload=wl, arrival=SMOKE_ARRIVAL, seed=0)
    header, loaded = load_trace(p1)
    assert header["n"] == len(specs) == len(loaded)
    # the header carries the full generating spec: a trace is
    # self-describing (Workload.from_spec reproduces the population)
    assert Workload.from_spec(header["workload"]) == wl
    save_trace(p2, loaded, workload=wl, arrival=SMOKE_ARRIVAL, seed=0)
    assert p1.read_bytes() == p2.read_bytes()


def test_load_trace_rejects_foreign_file(tmp_path):
    p = tmp_path / "x.jsonl"
    p.write_text('{"some": "json"}\n{"more": 1}\n')
    with pytest.raises(ValueError):
        load_trace(p)


def test_sample_prefix_stable_under_extension():
    """Request i's draw stream is independent of n: sampling 6 then 12
    yields the same first 6 requests (per-index seeded substreams, not
    one shared stream a later request could perturb)."""
    wl = mixed_chat(**SMOKE_WL)
    a = wl.sample(6, seed=7)
    b = wl.sample(12, seed=7)
    assert [s.to_json() for s in a] == [s.to_json() for s in b[:6]]


def test_different_seeds_differ():
    wl = mixed_chat(**SMOKE_WL)
    a = [s.to_json() for s in wl.sample(8, seed=0)]
    b = [s.to_json() for s in wl.sample(8, seed=1)]
    assert a != b


def test_shared_prefix_page_aligned_and_chain_hash_equal():
    """Cohort shared prefixes are whole pages and chain-hash equal
    across requests (the alignment the prefix cache and router
    affinity key on), stable across sample seeds; distinct cohorts get
    distinct prefixes."""
    from butterfly_tpu.cache.prefix import chain_block_hashes
    wl = mixed_chat(**SMOKE_WL)
    by_cohort = {}
    for seed in (0, 1):
        for s in wl.sample(24, seed):
            by_cohort.setdefault(s.cohort, []).append(s)
    chat, alt = by_cohort["chat"], by_cohort["chat_alt"]
    assert len(chat) >= 2 and len(alt) >= 1
    cohorts = {c.name: c for c in wl.cohorts}
    n_prefix = cohorts["chat"].shared_prefix_pages * wl.page_size
    assert n_prefix > 0 and n_prefix % wl.page_size == 0
    heads = {chain_block_hashes(s.tokens, wl.page_size, 1)[0]
             for s in chat}
    assert len(heads) == 1  # one shared first block across seeds
    alt_heads = {chain_block_hashes(s.tokens, wl.page_size, 1)[0]
                 for s in alt}
    assert heads != alt_heads


def test_workload_spec_roundtrip_samples_identically():
    wl = mixed_chat(**SMOKE_WL)
    wl2 = Workload.from_spec(wl.spec())
    assert [s.to_json() for s in wl.sample(8, 3)] == \
        [s.to_json() for s in wl2.sample(8, 3)]


# ---------------------------------------------------------------------------
# arrival processes
# ---------------------------------------------------------------------------


def test_poisson_interarrival_mean():
    """Poisson inter-arrival mean must track 1/rate (10% tolerance at
    n=4000) — this is the test that kills the mutcheck arrival-rate
    mutant (a process that ignores its rate samples mean 1.0s gaps)."""
    rate = 50.0
    ts = Poisson(rate).times(4000, seed=1)
    assert ts == sorted(ts) and ts[0] > 0
    gaps = [b - a for a, b in zip([0.0] + ts[:-1], ts)]
    mean = statistics.mean(gaps)
    assert abs(mean - 1.0 / rate) < 0.1 / rate
    # determinism
    assert ts == Poisson(rate).times(4000, seed=1)
    assert ts != Poisson(rate).times(4000, seed=2)


def test_burst_process_is_bursty():
    """MarkovOnOff gaps are bimodal: dense in-burst gaps at ~1/rate_on
    and off-phase silences near mean_off_s — unlike a Poisson stream of
    the same mean rate."""
    p = MarkovOnOff(rate_on=100.0, mean_on_s=0.5, mean_off_s=2.0)
    ts = p.times(600, seed=0)
    assert ts == sorted(ts)
    gaps = [b - a for a, b in zip([0.0] + ts[:-1], ts)]
    small = sum(1 for g in gaps if g < 5.0 / 100.0)
    assert small / len(gaps) > 0.8        # dense bursts dominate
    assert max(gaps) > 0.5                # but real silences exist
    # spec round-trip
    assert parse_arrival(p.spec()) == p


def test_ramp_accelerates():
    """Ramp arrivals speed up: the mean gap over the first quarter is
    larger than over the last quarter (rate0 < rate1)."""
    ts = Ramp(2.0, 50.0, 5.0).times(400, seed=0)
    gaps = [b - a for a, b in zip([0.0] + ts[:-1], ts)]
    q = len(gaps) // 4
    assert statistics.mean(gaps[:q]) > 2 * statistics.mean(gaps[-q:])


def test_parse_arrival_specs_and_errors():
    assert parse_arrival("poisson:8") == Poisson(8.0)
    assert parse_arrival("burst:20:0.5:2") == \
        MarkovOnOff(20.0, 0.5, 2.0, 0.0)
    assert parse_arrival("burst:20:0.5:2:1") == \
        MarkovOnOff(20.0, 0.5, 2.0, 1.0)
    assert parse_arrival("ramp:2:50:10") == Ramp(2.0, 50.0, 10.0)
    for bad in ("poisson", "poisson:0", "poisson:x", "burst:1:0:1",
                "drizzle:3", "ramp:1:2"):
        with pytest.raises(ValueError):
            parse_arrival(bad)


def test_assign_arrivals_stamps_schedule():
    wl, specs = smoke_specs(n=6)
    assert all(s.arrival_s >= 0 for s in specs)
    assert [s.arrival_s for s in specs] == sorted(s.arrival_s
                                                  for s in specs)


# ---------------------------------------------------------------------------
# server-level: preemption + shed through the real admission path
# ---------------------------------------------------------------------------


def _spin_server(rt: RuntimeConfig, slo_ttft_s=None):
    from http.server import ThreadingHTTPServer
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    sched = Scheduler(ServingEngine(model, params, rt),
                      slo_ttft_s=slo_ttft_s)
    state = ServerState(sched, ByteTokenizer())
    state.thread.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    return f"http://127.0.0.1:{httpd.server_port}", state, httpd


@pytest.fixture(scope="module")
def pressure_server():
    """Tiny replica whose page pool is the LARGEST single request's
    own need and no more: smoke_specs' twelve requests end at 4-10
    pages (prompt + max_new over pages of 8; 10 for the two prompts of
    48), 75 in all, so one request alone always fits and finishes (the
    tightest pool that can serve the burst at all), and nothing else
    does for long: the burst arrives within 8 ms, admission gives
    whatever is free to the next waiting prompt (2-6 pages), so from
    the first tick the pool is full while most of the burst still
    waits, and every running stream has 16-48 tokens (2-6 pages) left
    to grow: its next page can only come from a preemption. The
    pressure is then a property of pages against the working set,
    whatever order the burst's threads land in and however the ticks
    interleave with them. At 16 pages the first three or four prompts
    left room to grow into, and whether that room ran out depended on
    that order: eight replays on one idle machine counted 1, 3, 8 and
    15 preemptions at 16 pages and 5, 7, 18 and 20 at 10 (PR 41), and
    beside five other test workers 0 came up (the test failed at PRs
    35 and 40 and on the tree PR 41 started from). No SLO declared:
    admission never sheds, so the preemption pressure is undiluted."""
    rt = RuntimeConfig(max_batch_size=4, max_seq_len=112, page_size=8,
                       num_pages=10, prefix_caching=True,
                       decode_steps_per_tick=4, inflight_blocks=2,
                       prefill_max_batch=4)
    url, state, httpd = _spin_server(rt)
    yield url, state
    state.stop.set()
    httpd.shutdown()


def test_mixed_chat_replay_forces_preemption(pressure_server):
    """THE acceptance property (ROADMAP item 2): the canned mixed_chat
    workload, fired open-loop at a live server, drives
    serving_preemptions > 0 — and every preempted request still
    completes (recompute preemption is invisible to clients). The
    replay summary's ``server`` block (scraped /metrics) is where the
    preemptions show up: client-observed and server-counted outcomes
    in one artifact."""
    url, state = pressure_server
    wl, specs = smoke_specs(n=12, seed=0)
    out = replay_trace(url, specs, timeout=120.0)
    assert out["sent"] == 12
    assert out["outcomes"]["ok"] == 12, out["errors"]
    assert out["open_loop"] is True
    srv = out["server"]
    assert srv["scraped"] is True
    assert srv["serving_preemptions"] > 0
    # server counted every generated token the clients saw
    assert srv["tokens_generated_total"] >= sum(
        1 for _ in range(12))
    # the scheduler's own counter agrees with the scraped artifact
    assert state.sched.metrics()["preemptions_total"] == \
        srv["serving_preemptions"]
    # client-observed: no shed, no deadline — pure page pressure
    assert out["outcomes"]["shed_429"] == 0 == srv["shed_total"]


@pytest.fixture(scope="module")
def shed_server():
    """Replica with a declared (absurdly tight) TTFT objective: once
    latency evidence exists, predicted TTFT always busts 0.01 ms, so
    batch-priority arrivals shed deterministically (PR 8 semantics:
    batch sheds AT the objective; a cold server never sheds blind)."""
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    url, state, httpd = _spin_server(rt, slo_ttft_s=1e-5)
    yield url, state
    state.stop.set()
    httpd.shutdown()


def test_shed_429_through_admission_path(shed_server):
    """At least one 429 shed through the real PR-8 admission path
    (ServerState.submit -> shed_decision -> HTTP 429 + Retry-After),
    counted on BOTH sides of the wire: the replay summary's shed_429
    outcome and the scraped server shed_total match."""
    url, state = shed_server
    # evidence request: a finished multi-token request populates the
    # rolling ITL window predict_ttft reads (cold server never sheds)
    body = json.dumps({"tokens": [5, 7, 11], "max_tokens": 4,
                       "stop_token": -1}).encode()
    req = urllib.request.Request(url + "/generate", data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert len(json.loads(resp.read())["tokens"]) == 4
    assert state.sched.predict_ttft(4) is not None  # evidence exists
    specs = [RequestSpec(index=i, cohort="batch", tokens=[3, 1, 4],
                         max_new=4, priority="batch")
             for i in range(3)]
    out = replay_trace(url, specs, timeout=120.0)
    assert out["outcomes"]["shed_429"] >= 1
    srv = out["server"]
    assert srv["scraped"] and srv["shed_total"] >= 1
    assert srv["shed_total"] == out["outcomes"]["shed_429"]
    # sheds are terminal outcomes, not errors (loadgen exit semantics)
    assert out["outcomes"]["error"] == 0
    assert out["terminal"] == out["sent"]


# ---------------------------------------------------------------------------
# CLI smoke (tier-1-safe: seconds, not minutes)
# ---------------------------------------------------------------------------


def test_cli_workload_generate_deterministic(tmp_path):
    """`butterfly workload generate` smoke: writes a loadable trace,
    byte-identical across invocations (CI canary for the whole
    generate -> save chain)."""
    from butterfly_tpu.serve.cli import main
    args = ["workload", "generate", "--workload", "mixed_chat",
            "--n", "6", "--seed", "3", "--arrival", "poisson:50",
            "--page-size", "8", "--prompt-lo", "8", "--prompt-hi", "24",
            "--max-new-lo", "2", "--max-new-hi", "6", "--vocab", "258"]
    p1, p2 = tmp_path / "t1.jsonl", tmp_path / "t2.jsonl"
    assert main(args + ["--out", str(p1)]) == 0
    assert main(args + ["--out", str(p2)]) == 0
    assert p1.read_bytes() == p2.read_bytes()
    header, specs = load_trace(p1)
    assert header["n"] == 6 and len(specs) == 6
    assert header["arrival"] == "poisson:50"


def test_cli_workload_replay_smoke(tmp_path, pressure_server):
    """`butterfly workload replay` smoke against a live replica: the
    saved trace fires and every request reaches a terminal outcome."""
    from butterfly_tpu.serve.cli import main
    url, _ = pressure_server
    p = tmp_path / "t.jsonl"
    assert main(["workload", "generate", "--workload", "mixed_chat",
                 "--n", "4", "--seed", "1", "--arrival", "poisson:50",
                 "--page-size", "8", "--prompt-lo", "8",
                 "--prompt-hi", "24", "--max-new-lo", "2",
                 "--max-new-hi", "6", "--vocab", "258",
                 "--out", str(p)]) == 0
    assert main(["workload", "replay", "--trace", str(p),
                 "--url", url, "--speed", "50"]) == 0


def test_loadgen_open_loop_workload_mode(pressure_server):
    """tools/loadgen.py --workload: the open-loop mode generates,
    schedules, and fires a workload end to end, and its summary folds
    the scraped server counters in (satellite 2)."""
    import importlib
    import sys
    from pathlib import Path
    url, _ = pressure_server
    tools = str(Path(__file__).resolve().parents[1] / "tools")
    sys.path.insert(0, tools)
    try:
        lg = importlib.import_module("loadgen")
    finally:
        sys.path.remove(tools)
    rc = lg.main(["--url", url, "--workload", "mixed_chat", "--n", "4",
                  "--seed", "2", "--arrival", "poisson:50",
                  "--speed", "50", "--page-size", "8",
                  "--prompt-lo", "8", "--prompt-hi", "24",
                  "--max-new-lo", "2", "--max-new-hi", "6",
                  "--vocab", "258", "--json"])
    assert rc == 0
