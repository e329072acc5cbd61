"""Multi-replica router tests: prefix affinity, health-aware failover,
streaming passthrough (ISSUE 2).

Fast tier: everything runs in-process — two tiny-model `serve` replicas
behind one router, plus stdlib stub backends for the failure-injection
cases (a replica that dies mid-stream, a port with nothing listening).
"""
import http.client
import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace

import jax
import pytest

from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.obs.registry import MetricsRegistry
from butterfly_tpu.router.policy import (
    HashRing, PrefixAffinityPolicy, affinity_key)
from butterfly_tpu.router.pool import ReplicaPool
from butterfly_tpu.router.proxy import (
    RouterState, extract_route_tokens, make_router_handler)
from butterfly_tpu.sched.scheduler import Scheduler
from butterfly_tpu.serve.server import ServerState, make_handler
from butterfly_tpu.utils.tokenizer import ByteTokenizer

CFG = tiny("llama", dtype="float32", param_dtype="float32")
PAGE = 8
AFF_BLOCKS = 4  # affinity key hashes the leading 4 full pages (32 toks)


def _start_replica():
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=PAGE,
                       num_pages=24, prefix_caching=True)
    sched = Scheduler(ServingEngine(model, params, rt))
    state = ServerState(sched, ByteTokenizer())
    state.thread.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return SimpleNamespace(state=state, httpd=httpd, sched=sched,
                           rid=f"127.0.0.1:{httpd.server_port}",
                           url=f"http://127.0.0.1:{httpd.server_port}")


def _start_router(backends, **kw):
    registry = MetricsRegistry()
    pool = ReplicaPool(backends, probe_interval=0.2, registry=registry,
                       **kw)
    policy = PrefixAffinityPolicy(pool, page_size=PAGE,
                                  affinity_blocks=AFF_BLOCKS)
    state = RouterState(pool, policy, registry=registry,
                        read_timeout=120.0)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_router_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return SimpleNamespace(pool=pool, policy=policy, state=state,
                           httpd=httpd,
                           url=f"http://127.0.0.1:{httpd.server_port}")


@pytest.fixture(scope="module")
def cluster():
    """Two real tiny-model replicas behind one router. The pool's prober
    runs so health scrapes happen, but replicas start optimistically
    live — tests never wait on a probe cycle."""
    reps = [_start_replica(), _start_replica()]
    router = _start_router([r.rid for r in reps])
    router.pool.start()
    yield SimpleNamespace(router=router, reps=reps,
                          by_rid={r.rid: r for r in reps})
    router.pool.stop()
    router.httpd.shutdown()
    for r in reps:
        r.state.stop.set()
        r.httpd.shutdown()


def post(url, path, obj, raw=False, timeout=120):
    req = urllib.request.Request(
        url + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp if raw else (json.loads(resp.read()), resp.headers)


def get(url, path):
    return urllib.request.urlopen(url + path, timeout=30).read().decode()


# -- pure-logic units --------------------------------------------------------

def test_hash_ring_stability():
    """Removing one replica only remaps ITS arc: keys whose target
    survives keep their target (the property that preserves every other
    replica's warm cache on failover)."""
    rids = ["10.0.0.1:8000", "10.0.0.2:8000", "10.0.0.3:8000"]
    ring3 = HashRing(rids)
    ring2 = HashRing([rids[0], rids[2]])
    import hashlib
    moved = kept = 0
    for i in range(200):
        key = hashlib.sha256(b"key-%d" % i).digest()
        before = ring3.ordered(key)[0]
        after = ring2.ordered(key)[0]
        if before == rids[1]:
            moved += 1
            assert after in (rids[0], rids[2])
        else:
            kept += 1
            assert after == before, "surviving replica's key moved"
    assert moved > 0 and kept > 0  # both populations exercised


def test_hash_ring_failover_order_is_deterministic():
    ring = HashRing(["a:1", "b:1", "c:1"])
    key = b"\x42" * 32
    assert ring.ordered(key) == ring.ordered(key)
    assert sorted(ring.ordered(key)) == ["a:1", "b:1", "c:1"]


def test_affinity_key_block_granularity():
    """Same leading blocks -> same key regardless of tail; differing
    within the first block -> different key."""
    base = list(range(1, 1 + AFF_BLOCKS * PAGE))
    k1 = affinity_key(base + [7, 8, 9], PAGE, AFF_BLOCKS)
    k2 = affinity_key(base + [200, 201], PAGE, AFF_BLOCKS)
    assert k1 == k2
    changed = [99] + base[1:]
    assert affinity_key(changed, PAGE, AFF_BLOCKS) != k1
    # sub-block prompts still deterministic, and empty -> None
    assert affinity_key([1, 2], PAGE, AFF_BLOCKS) == \
        affinity_key([1, 2], PAGE, AFF_BLOCKS)
    assert affinity_key([], PAGE, AFF_BLOCKS) is None
    assert affinity_key(None, PAGE, AFF_BLOCKS) is None


def test_affinity_key_matches_prefix_cache_blocks():
    """The routing key IS the allocator's chain hash for the same
    blocks — the alignment that makes affinity line up with page
    reuse."""
    from butterfly_tpu.cache.prefix import chain_block_hashes
    toks = list(range(1, 1 + AFF_BLOCKS * PAGE + 5))
    assert affinity_key(toks, PAGE, AFF_BLOCKS) == \
        chain_block_hashes(toks, PAGE, AFF_BLOCKS)[-1]


def test_extract_route_tokens():
    def enc(obj):
        return json.dumps(obj).encode()
    assert extract_route_tokens(enc({"tokens": [1, 2, 3]})) == [1, 2, 3]
    assert extract_route_tokens(enc({"prompt": [4, 5]})) == [4, 5]
    assert extract_route_tokens(enc({"prompt": "hi"})) == [104, 105]
    assert extract_route_tokens(b"not json") is None
    assert extract_route_tokens(enc({"prompt": 7})) is None
    assert extract_route_tokens(b"") is None


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_pool_degrades_then_dead_with_backoff():
    """Consecutive connect failures walk live -> degraded -> dead; dead
    re-probes are scheduled with jittered exponential backoff."""
    pool = ReplicaPool([f"127.0.0.1:{_free_port()}"], dead_after=3,
                       backoff_base=0.5, backoff_max=10.0)
    (r,) = pool.replicas.values()
    assert r.state == "live"  # optimistic until evidence
    pool.probe_one(r)
    assert r.state == "degraded" and r.fails == 1
    pool.probe_one(r)
    assert r.state == "degraded" and r.fails == 2
    t0 = time.monotonic()
    pool.probe_one(r)
    assert r.state == "dead" and r.fails == 3
    delay = r.next_probe_t - t0
    # base * 2^0 = 0.5s, jittered x[0.5, 1.5)
    assert 0.2 <= delay <= 0.8
    pool.probe_one(r)  # deeper backoff grows the delay window
    assert r.next_probe_t - time.monotonic() <= 10.0 * 1.5
    assert pool.candidates() == []  # dead members are never candidates


def test_pool_parses_health_load_signal(cluster):
    pool = cluster.router.pool
    pool.probe_all()
    for snap in pool.snapshot():
        assert snap["state"] == "live"
        assert snap["queue_depth"] >= 0 and snap["active"] >= 0


# -- routing through real replicas ------------------------------------------

def test_proxy_roundtrip_and_replica_header(cluster):
    out, headers = post(cluster.router.url, "/generate",
                        {"tokens": [5, 7, 11], "max_tokens": 4,
                         "stop_token": -1})
    assert len(out["tokens"]) == 4
    assert headers["X-Routed-To"] in cluster.by_rid
    # determinism through the router (both replicas share weights)
    again, _ = post(cluster.router.url, "/generate",
                    {"tokens": [5, 7, 11], "max_tokens": 4,
                     "stop_token": -1})
    assert again["tokens"] == out["tokens"]


def test_request_id_echoes_through_router(cluster):
    req = urllib.request.Request(
        cluster.router.url + "/generate",
        data=json.dumps({"tokens": [9, 9], "max_tokens": 2,
                         "stop_token": -1}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "rte-42"})
    resp = urllib.request.urlopen(req, timeout=120)
    resp.read()
    assert resp.headers["X-Request-Id"] == "rte-42"


def test_same_prefix_lands_on_same_replica_and_hits_cache(cluster):
    """Two same-prefix requests route to one replica and the second is
    served from its prefix cache (hit counter rises THERE)."""
    prefix = [(13 * i) % 250 + 1 for i in range(AFF_BLOCKS * PAGE)]
    before = {r.rid: r.sched.alloc.hit_tokens for r in cluster.reps}
    _, h1 = post(cluster.router.url, "/generate",
                 {"tokens": prefix + [3, 1], "max_tokens": 2,
                  "stop_token": -1})
    _, h2 = post(cluster.router.url, "/generate",
                 {"tokens": prefix + [4, 2], "max_tokens": 2,
                  "stop_token": -1})
    rid = h1["X-Routed-To"]
    assert h2["X-Routed-To"] == rid, "same prefix must share a replica"
    hit = cluster.by_rid[rid].sched.alloc.hit_tokens - before[rid]
    assert hit >= AFF_BLOCKS * PAGE, \
        f"second request should hit the shared prefix pages, got {hit}"
    other = next(r for r in cluster.reps if r.rid != rid)
    assert other.sched.alloc.hit_tokens == before[other.rid]
    # and the router counted the affinity routing
    text = get(cluster.router.url, "/metrics")
    aff = [l for l in text.splitlines()
           if l.startswith("butterfly_router_affinity_hits_total ")]
    assert aff and float(aff[0].split()[-1]) >= 2


def test_affinity_beats_round_robin_under_shared_load(cluster, loadgen):
    """ISSUE 2 acceptance: 50% shared-prefix workload -> prefix hits
    concentrate on the affinity replica, zero failed requests."""
    before = {r.rid: r.sched.alloc.hit_tokens for r in cluster.reps}
    stats = loadgen.run_load(cluster.router.url, clients=3,
                             requests_per_client=4, prefix_share=0.5,
                             shared_len=AFF_BLOCKS * PAGE, tail_len=4,
                             max_tokens=4, seed=7, vocab=64)
    assert stats["failed"] == 0, stats["errors"]
    assert stats["ok"] == 12
    assert stats["shared_prefix_requests"] >= 2  # workload sanity
    hits = {r.rid: r.sched.alloc.hit_tokens - before[r.rid]
            for r in cluster.reps}
    hot = max(hits.values())
    cold = min(hits.values())
    # every shared-prefix request after the first hits the one affinity
    # replica; round-robin would split them (and halve per-replica hits)
    assert hot >= (stats["shared_prefix_requests"] - 1) * AFF_BLOCKS * PAGE
    assert hot > 2 * cold, f"hits not concentrated: {hits}"
    # every request was routed and tagged (X-Routed-To accounting)
    assert sum(stats["by_replica"].values()) == 12, stats["by_replica"]


def test_sse_stream_through_router_byte_identical(cluster):
    """Router-proxied SSE == direct-to-replica SSE after de-chunking."""
    body = {"tokens": [21, 22, 23], "max_tokens": 3, "stream": True,
            "stop_token": -1}
    via_router = post(cluster.router.url, "/generate", body,
                      raw=True)
    routed_to = via_router.headers["X-Routed-To"]
    router_bytes = via_router.read()
    direct = post(cluster.by_rid[routed_to].url, "/generate", body,
                  raw=True)
    assert direct.read() == router_bytes
    assert via_router.headers["Content-Type"] == "text/event-stream"
    events = [l[6:] for l in router_bytes.split(b"\n")
              if l.startswith(b"data: ")]
    assert events[-1] == b"[DONE]" and len(events) == 4


def test_openai_completions_through_router(cluster):
    out, headers = post(cluster.router.url, "/v1/completions",
                        {"prompt": [5, 7, 11], "max_tokens": 3,
                         "stop_token": -1})
    assert out["object"] == "text_completion"
    assert headers["X-Routed-To"] in cluster.by_rid


def test_backend_4xx_forwarded_not_retried(cluster):
    before = cluster.router.state._c_retry.value
    with pytest.raises(urllib.error.HTTPError) as e:
        post(cluster.router.url, "/generate",
             {"tokens": [999999], "max_tokens": 2})
    assert e.value.code == 400
    assert json.loads(e.value.read())["error"] == "token id out of range"
    assert cluster.router.state._c_retry.value == before


def test_router_replicas_and_drain_workflow(cluster):
    body = json.loads(get(cluster.router.url, "/router/replicas"))
    assert {s["replica"] for s in body["replicas"]} == \
        set(cluster.by_rid)
    target = cluster.reps[0].rid
    out, _ = post(cluster.router.url, "/router/drain",
                  {"replica": target})
    assert out["state"] == "draining"
    try:
        for i in range(4):  # varied prompts: all must avoid the drained
            _, h = post(cluster.router.url, "/generate",
                        {"tokens": [40 + i, 41 + i], "max_tokens": 2,
                         "stop_token": -1})
            assert h["X-Routed-To"] != target
    finally:
        out, _ = post(cluster.router.url, "/router/undrain",
                      {"replica": target})
    assert out["state"] in ("live", "degraded")
    # unknown replica -> 404
    with pytest.raises(urllib.error.HTTPError) as e:
        post(cluster.router.url, "/router/drain", {"replica": "nope:1"})
    assert e.value.code == 404


def test_router_metrics_families(cluster):
    text = get(cluster.router.url, "/metrics")
    assert "# TYPE butterfly_router_requests_total counter" in text
    assert 'butterfly_router_requests_total{replica="' in text
    assert 'outcome="ok"' in text
    assert "butterfly_router_retries_total" in text
    assert "butterfly_router_affinity_hits_total" in text
    assert 'butterfly_router_outstanding_requests{replica="' in text
    # router health rolls up the pool
    health = json.loads(get(cluster.router.url, "/health"))
    assert health["status"] == "ok" and health["replicas_live"] >= 1


# -- failover ---------------------------------------------------------------

def _tokens_targeting(router, rid, length=AFF_BLOCKS * PAGE):
    """Deterministically find a token prompt whose affinity target is
    `rid` (ring lookup is pure, so this is not a race)."""
    for t in range(1, 300):
        cand, _ = router.policy.plan([t % 250 + 1] * length)
        if cand and cand[0].rid == rid:
            return [t % 250 + 1] * length
    raise AssertionError(f"no prompt maps to {rid}")


def test_connect_refused_fails_over_with_zero_failures(cluster):
    """A dead-port backend (replica SIGKILLed and gone) never fails an
    un-started request: the router retries it onto the survivor."""
    dead = f"127.0.0.1:{_free_port()}"
    live = cluster.reps[0]
    router = _start_router([dead, live.rid])  # no prober: optimistic
    try:
        # a prompt whose affinity target is the dead member: first
        # attempt is refused, the retry lands on the survivor
        toks = _tokens_targeting(router, dead)
        out, h = post(router.url, "/generate",
                      {"tokens": toks, "max_tokens": 2,
                       "stop_token": -1})
        assert len(out["tokens"]) == 2
        assert h["X-Routed-To"] == live.rid
        assert router.state._c_retry.value >= 1
        # the connect failure derouted the corpse immediately: varied
        # follow-ups all succeed without touching it
        for i in range(5):
            out, h = post(router.url, "/generate",
                          {"tokens": [60 + i] * 8, "max_tokens": 2,
                           "stop_token": -1})
            assert len(out["tokens"]) == 2
            assert h["X-Routed-To"] == live.rid
        snap = {s["replica"]: s for s in router.pool.snapshot()}
        assert snap[dead]["state"] in ("degraded", "dead")
        assert snap[live.rid]["state"] == "live"
    finally:
        router.httpd.shutdown()


def test_replica_killed_between_requests_fails_over(cluster):
    """Kill one of two stub replicas mid-run: subsequent requests all
    succeed on the survivor (zero failed un-started requests)."""
    a, b = _StubReplica(), _StubReplica()
    router = _start_router([a.rid, b.rid])
    try:
        for i in range(4):
            post(router.url, "/generate",
                 {"tokens": [i + 1, i + 2], "max_tokens": 1})
        a.kill()  # hard stop: connects now refused
        for i in range(6):
            out, h = post(router.url, "/generate",
                          {"tokens": [70 + i] * 8, "max_tokens": 1})
            assert h["X-Routed-To"] == b.rid
        assert a.hits + b.hits == 10
    finally:
        router.httpd.shutdown()
        b.kill()


class _StubReplica:
    """Minimal backend speaking the serve protocol shape: JSON
    /generate, 200 /health. Counts requests; kill() frees the port."""

    def __init__(self):
        outer = self
        self.hits = 0

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _json(self, code, obj):
                data = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                self._json(200, {"status": "ok", "queue_depth": 0,
                                 "active": 0})

            def do_POST(self):
                outer.hits += 1
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                self._json(200, {"tokens": [1], "text": "x",
                                 "ttft_s": 0.0, "total_s": 0.0})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.rid = f"127.0.0.1:{self.httpd.server_port}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def kill(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class _DyingStreamReplica:
    """Backend that starts an SSE stream then dies mid-flight (socket
    closed without the terminating chunk) — the SIGKILL-mid-stream
    case."""

    def __init__(self, events_before_death=2):
        outer = self
        self.hits = 0

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                data = json.dumps({"status": "ok", "queue_depth": 0,
                                   "active": 0}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_POST(self):
                outer.hits += 1
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                for i in range(events_before_death):
                    payload = (b"data: " + json.dumps(
                        {"token": i, "text": "t"}).encode() + b"\n\n")
                    self.wfile.write(
                        f"{len(payload):X}\r\n".encode() + payload
                        + b"\r\n")
                    self.wfile.flush()
                # die: a real FIN with NO terminating 0-chunk (plain
                # close() would leak the fd via rfile/wfile references
                # and leave the router blocked instead of truncated)
                self.connection.shutdown(socket.SHUT_RDWR)
                self.close_connection = True

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.rid = f"127.0.0.1:{self.httpd.server_port}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()


def test_midstream_death_truncates_and_never_retries():
    """Bytes already sent -> the router must PROPAGATE the truncation,
    not re-run the request on the healthy replica (a retry would
    duplicate tokens the client already consumed)."""
    dying = _DyingStreamReplica()
    healthy = _StubReplica()
    router = _start_router([dying.rid, healthy.rid])
    try:
        # a prompt whose affinity target is the dying replica, so the
        # stream provably starts there (deterministic ring lookup)
        tokens = _tokens_targeting(router, dying.rid)
        host, port = router.url[len("http://"):].split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        conn.request("POST", "/generate",
                     body=json.dumps({"tokens": tokens, "max_tokens": 8,
                                      "stream": True}).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Routed-To") == dying.rid
        with pytest.raises((http.client.IncompleteRead,
                            ConnectionError, OSError)):
            # the partial events arrive, then the truncation surfaces as
            # an incomplete chunked body — NOT a clean EOF
            while True:
                if resp.read1(65536) == b"":
                    raise AssertionError(
                        "stream ended cleanly; truncation was masked")
        conn.close()
        assert healthy.hits == 0, \
            "mid-stream failure must never be retried"
        assert dying.hits == 1
        snap = {s["replica"]: s for s in router.pool.snapshot()}
        assert snap[dying.rid]["state"] in ("degraded", "dead")
    finally:
        router.httpd.shutdown()
        dying.httpd.shutdown()
        healthy.kill()


def test_wedged_503_is_retried_before_first_byte():
    """A wedged replica (503s everything) costs a retry, not a failure."""

    class _Wedged:
        def __init__(self):
            outer = self
            self.hits = 0

            class H(BaseHTTPRequestHandler):
                protocol_version = "HTTP/1.1"

                def log_message(self, fmt, *args):
                    pass

                def _json(self, code, obj):
                    data = json.dumps(obj).encode()
                    self.send_response(code)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)

                def do_GET(self):
                    self._json(503, {"status": "error",
                                     "detail": "wedged"})

                def do_POST(self):
                    outer.hits += 1
                    n = int(self.headers.get("Content-Length", 0))
                    self.rfile.read(n)
                    self._json(503, {"error": "server wedged: boom"})

            self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
            self.rid = f"127.0.0.1:{self.httpd.server_port}"
            threading.Thread(target=self.httpd.serve_forever,
                             daemon=True).start()

    wedged = _Wedged()
    healthy = _StubReplica()
    router = _start_router([wedged.rid, healthy.rid])
    try:
        # first request provably targets the wedged member: its 503 is
        # retried (no client bytes yet) onto the healthy one
        toks = _tokens_targeting(router, wedged.rid)
        _, h = post(router.url, "/generate",
                    {"tokens": toks, "max_tokens": 1})
        assert h["X-Routed-To"] == healthy.rid
        assert wedged.hits == 1
        for i in range(5):
            _, h = post(router.url, "/generate",
                        {"tokens": [80 + i] * 8, "max_tokens": 1})
            assert h["X-Routed-To"] == healthy.rid
        snap = {s["replica"]: s for s in router.pool.snapshot()}
        assert snap[wedged.rid]["state"] == "degraded"
        assert wedged.hits == 1, \
            "wedge feedback should deroute after the first 503"
    finally:
        router.httpd.shutdown()
        wedged.httpd.shutdown()
        healthy.kill()


def test_no_routable_replicas_is_503_with_retry_after():
    dead1 = f"127.0.0.1:{_free_port()}"
    dead2 = f"127.0.0.1:{_free_port()}"
    router = _start_router([dead1, dead2], dead_after=1)
    try:
        router.pool.probe_all()  # both marked dead immediately
        with pytest.raises(urllib.error.HTTPError) as e:
            post(router.url, "/generate",
                 {"tokens": [1, 2], "max_tokens": 1})
        assert e.value.code == 503
        assert e.value.headers["Retry-After"] == "1"
    finally:
        router.httpd.shutdown()
