"""HTTP serving tests: in-process server on an ephemeral port.

Covers /generate (blocking + SSE streaming + token-id path), /metrics
prometheus output, /health, and input validation.
"""
import json
import threading
import time
import urllib.request

import jax
import pytest

from butterfly_tpu.core.config import RuntimeConfig, tiny
from butterfly_tpu.engine.serving import ServingEngine
from butterfly_tpu.models.common import Model
from butterfly_tpu.sched.scheduler import Scheduler
from butterfly_tpu.serve.server import ServerState, make_handler
from butterfly_tpu.utils.tokenizer import ByteTokenizer

CFG = tiny("llama", dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def server():
    from http.server import ThreadingHTTPServer
    from butterfly_tpu.obs.ticklog import FlightRecorder
    from butterfly_tpu.obs.trace import Tracer
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(model, params, rt), tracer=Tracer(),
                      flightrec=FlightRecorder())
    state = ServerState(sched, ByteTokenizer())
    state.thread.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_port}"
    state.stop.set()
    httpd.shutdown()


def post(url, path, obj, raw=False):
    req = urllib.request.Request(
        url + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    resp = urllib.request.urlopen(req, timeout=120)
    return resp if raw else json.loads(resp.read())


def get(url, path):
    return urllib.request.urlopen(url + path, timeout=30).read().decode()


def test_health(server):
    body = json.loads(get(server, "/health"))
    assert body["status"] == "ok"
    # load signal for the router's least-loaded policy: one cheap JSON
    # probe instead of a Prometheus text scrape
    assert isinstance(body["queue_depth"], int) and body["queue_depth"] >= 0
    assert isinstance(body["active"], int) and body["active"] >= 0
    # which layout the KV pool has (a model without an indexer: a row a
    # KV head), beside the kernels and the allocator
    assert body["pool_layout"] == "head" and "kernels" in body


def test_generate_blocking(server):
    out = post(server, "/generate",
               {"prompt": "hi", "max_tokens": 4, "stop_token": -1})
    assert len(out["tokens"]) == 4
    assert out["ttft_s"] >= 0 and out["total_s"] > 0


def test_speculative_body_knob(server):
    """Per-request speculation opt-out: accepted (and inert) on a
    non-speculating server, rejected when not a boolean."""
    out = post(server, "/generate",
               {"tokens": [5, 7, 11], "max_tokens": 4, "stop_token": -1,
                "speculative": False})
    assert len(out["tokens"]) == 4
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/generate",
             {"tokens": [5, 7], "max_tokens": 2, "speculative": "yes"})
    assert e.value.code == 400


def test_generate_token_ids_deterministic(server):
    a = post(server, "/generate",
             {"tokens": [5, 7, 11], "max_tokens": 5, "stop_token": -1})
    b = post(server, "/generate",
             {"tokens": [5, 7, 11], "max_tokens": 5, "stop_token": -1})
    assert a["tokens"] == b["tokens"]


def test_generate_stream(server):
    resp = post(server, "/generate",
                {"prompt": "ab", "max_tokens": 3, "stream": True,
                 "stop_token": -1}, raw=True)
    assert resp.headers["Content-Type"] == "text/event-stream"
    events = []
    for line in resp:
        line = line.strip()
        if line.startswith(b"data: "):
            events.append(line[6:])
    assert events[-1] == b"[DONE]"
    toks = [json.loads(e)["token"] for e in events[:-1]]
    assert len(toks) == 3


def test_concurrent_clients(server):
    results = {}

    def hit(name, prompt):
        results[name] = post(server, "/generate",
                             {"tokens": prompt, "max_tokens": 4,
                              "stop_token": -1})
    threads = [threading.Thread(target=hit, args=(i, [i + 1, i + 2]))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 4
    # determinism: same prompt again matches
    again = post(server, "/generate",
                 {"tokens": [1, 2], "max_tokens": 4, "stop_token": -1})
    assert results[0]["tokens"] == again["tokens"]


def test_metrics_endpoint(server):
    text = get(server, "/metrics")
    assert "butterfly_requests_total" in text
    assert "# TYPE butterfly_tokens_generated_total counter" in text
    assert "butterfly_kv_pages_free" in text


def test_metrics_histograms_well_formed(server):
    # at least one request must have completed for ttft to be observed
    post(server, "/generate",
         {"tokens": [2, 3], "max_tokens": 3, "stop_token": -1})
    text = get(server, "/metrics")
    assert "# TYPE butterfly_ttft_seconds histogram" in text
    for name in ("ttft_seconds", "queue_wait_seconds", "batch_size",
                 "prefill_tokens"):
        full = f"butterfly_{name}"
        buckets = [l for l in text.splitlines()
                   if l.startswith(full + "_bucket")]
        assert buckets, f"missing {full}_bucket series"
        assert buckets[-1].startswith(full + '_bucket{le="+Inf"}')
    # cumulative monotonicity + _count == +Inf bucket, per histogram
    import re as _re
    for name in ("ttft_seconds", "queue_wait_seconds"):
        full = f"butterfly_{name}"
        vals = [int(m.group(1)) for m in _re.finditer(
            _re.escape(full) + r'_bucket\{le="[^"]+"\} (\d+)', text)]
        assert vals == sorted(vals)
        count = int(_re.search(
            _re.escape(full) + r"_count (\d+)", text).group(1))
        assert vals[-1] == count and count >= 1
        assert _re.search(_re.escape(full) + r"_sum \d", text)
    # a metric name never appears with two TYPE declarations
    types = [l.split()[2] for l in text.splitlines()
             if l.startswith("# TYPE")]
    assert len(types) == len(set(types))


def test_debug_requests_timeline(server):
    # drive a STREAMED request with a client id, then read its timeline
    resp = post(server, "/generate",
                {"tokens": [4, 5, 6], "max_tokens": 4, "stream": True,
                 "stop_token": -1, "request_id": "dbg-stream-1"}, raw=True)
    for _ in resp:  # drain the SSE body to completion
        pass
    body = json.loads(get(server, "/debug/requests"))
    assert body["enabled"] is True
    mine = [r for r in body["requests"]
            if r["request_id"] == "dbg-stream-1"]
    assert len(mine) == 1
    events = mine[0]["events"]
    names = [e["name"] for e in events]
    # acceptance: admit, prefill, first-token, finish present, in order
    for needed in ("submit", "admit", "prefill_done", "first_token",
                   "finish"):
        assert needed in names, f"missing {needed} in {names}"
    assert names.index("admit") < names.index("prefill_done") \
        < names.index("first_token") < names.index("finish")
    ts = [e["t"] for e in events]
    assert ts == sorted(ts), "timestamps must be monotonic"
    fin = events[names.index("finish")]
    assert fin["state"] == "finished" and fin["tokens"] == 4
    # ?n= limits the window
    limited = json.loads(get(server, "/debug/requests?n=1"))
    assert len(limited["requests"]) == 1


def test_debug_requests_header_id_passthrough(server):
    req = urllib.request.Request(
        server + "/generate",
        data=json.dumps({"tokens": [9], "max_tokens": 2,
                         "stop_token": -1}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "hdr-77"})
    json.loads(urllib.request.urlopen(req, timeout=120).read())
    body = json.loads(get(server, "/debug/requests"))
    assert any(r["request_id"] == "hdr-77" for r in body["requests"])


def test_debug_requests_request_id_filter(server):
    """?request_id= narrows the dump to ONE distributed request's
    timelines (the fleet trace-merge fetch), drops the global ring, and
    still carries the wall-clock anchors offline tools align on."""
    for rid in ("filt-a", "filt-b"):
        post(server, "/generate", {"tokens": [3, 5], "max_tokens": 2,
                                   "stop_token": -1, "request_id": rid})
    body = json.loads(get(server, "/debug/requests?request_id=filt-a"))
    assert body["enabled"] is True
    assert [r["request_id"] for r in body["requests"]] == ["filt-a"]
    assert body["global_events"] == []  # one request's view, no ticks
    assert body["t0_wall"] > 0 and body["t0_monotonic"] >= 0
    missing = json.loads(get(server, "/debug/requests?request_id=nope"))
    assert missing["requests"] == []


def test_health_carries_wall_clock(server):
    """/health stamps now_wall — the prober's clock-offset input."""
    import time
    body = json.loads(get(server, "/health"))
    assert abs(body["now_wall"] - time.time()) < 60


def test_validation_errors(server):
    for body, code in [({"prompt": ""}, 400),
                       ({"tokens": [999999]}, 400),
                       ({"tokens": [1], "max_tokens": 10000}, 400)]:
        try:
            post(server, "/generate", body)
            raised = None
        except urllib.error.HTTPError as e:  # noqa: F821
            raised = e.code
        assert raised == code


import urllib.error  # noqa: E402


def test_scheduler_crash_degrades_health():
    """A tick() exception must not wedge the server: waiters unblock,
    /health goes 503, new submissions are rejected."""
    import queue as _q
    from butterfly_tpu.serve.server import ServerState
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    rt = RuntimeConfig(max_batch_size=1, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(model, params, rt))
    state = ServerState(sched, ByteTokenizer())

    calls = {"n": 0}
    def boom():
        calls["n"] += 1
        raise RuntimeError("device on fire")
    sched.tick = boom
    state.thread.start()
    req, q = state.submit([1, 2], 4, 0.0, -1)
    assert q.get(timeout=10) is None        # sentinel: waiter unblocked
    assert req.state == "cancelled"
    assert "device on fire" in state.error
    # wedged: further admissions are rejected loudly (handler -> 503),
    # never queued onto the presumed-dead device
    with pytest.raises(RuntimeError, match="wedged"):
        state.submit([1], 2, 0.0, -1)
    state.stop.set()


def test_preemption_prefers_youngest():
    """Older request keeps its pages; the newcomer preempts itself."""
    from butterfly_tpu.sched.scheduler import Scheduler as S
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    # pool: 5 usable pages of 4 -> two requests to ~16 tokens can't coexist
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=32, page_size=4,
                       num_pages=5)
    sched = S(ServingEngine(model, params, rt))
    r_old = sched.submit([5, 7, 11], max_new_tokens=12)
    sched.tick()
    r_new = sched.submit([3, 1], max_new_tokens=12)
    sched.run_until_done(max_ticks=400)
    assert r_old.state == "finished" and r_new.state == "finished"
    assert r_old.preemptions == 0          # the older one is never evicted
    assert r_new.preemptions > 0


def test_max_new_tokens_alias(server):
    out = post(server, "/generate",
               {"prompt": "hi", "max_new_tokens": 3, "stop_token": -1})
    assert len(out["tokens"]) == 3


def test_openai_completions_blocking(server):
    out = post(server, "/v1/completions",
               {"prompt": "hi", "max_tokens": 4, "stop_token": -1})
    assert out["object"] == "text_completion"
    assert out["id"].startswith("cmpl-")
    assert out["model"] == "butterfly"
    (choice,) = out["choices"]
    assert choice["index"] == 0 and choice["finish_reason"] == "length"
    assert isinstance(choice["text"], str)
    assert out["usage"]["completion_tokens"] == 4
    assert out["usage"]["total_tokens"] == (
        out["usage"]["prompt_tokens"] + 4)


def test_openai_completions_token_prompt_matches_generate(server):
    a = post(server, "/v1/completions",
             {"prompt": [5, 7, 11], "max_tokens": 5, "stop_token": -1})
    b = post(server, "/generate",
             {"tokens": [5, 7, 11], "max_tokens": 5, "stop_token": -1})
    assert a["choices"][0]["text"] == b["text"]


def test_openai_completions_stream(server):
    resp = post(server, "/v1/completions",
                {"prompt": "ab", "max_tokens": 3, "stream": True,
                 "stop_token": -1}, raw=True)
    assert resp.headers["Content-Type"] == "text/event-stream"
    events = []
    for line in resp:
        line = line.strip()
        if line.startswith(b"data: "):
            events.append(line[6:])
    assert events[-1] == b"[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    # 3 token chunks + 1 final finish_reason chunk
    assert len(chunks) == 4
    assert all(c["object"] == "text_completion" for c in chunks)
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert all(c["choices"][0]["finish_reason"] is None for c in chunks[:-1])


def test_openai_completions_rejects_multi_choice(server):
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/completions",
             {"prompt": "hi", "max_tokens": 2, "n": 3})
    assert e.value.code == 400


def test_openai_completions_malformed_n_is_400(server):
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/completions",
             {"prompt": "hi", "max_tokens": 2, "n": None})
    assert e.value.code == 400


def test_openai_completions_stop_token_excluded_from_text(server):
    # Discover the greedy continuation, then stop on its first token
    # value that did NOT already occur earlier in the continuation:
    # picking a fixed index broke when the tiny model's greedy chain
    # settled into a repeat (the "3rd token" then also matched token 1
    # and generation legitimately stopped there with empty text).
    ref = post(server, "/generate",
               {"tokens": [5, 7, 11], "max_tokens": 6, "stop_token": -1})
    idx = next((i for i, t in enumerate(ref["tokens"])
                if i > 0 and t not in ref["tokens"][:i]), None)
    if idx is None:
        import pytest
        pytest.skip("greedy continuation is a single repeated token: "
                    "no stop position can leave preceding text")
    stop = ref["tokens"][idx]
    out = post(server, "/v1/completions",
               {"prompt": [5, 7, 11], "max_tokens": 6, "stop_token": stop})
    (choice,) = out["choices"]
    assert choice["finish_reason"] == "stop"
    # stop marker excluded from text; usage still counts it
    from butterfly_tpu.utils.tokenizer import ByteTokenizer
    want_text = ByteTokenizer().decode(ref["tokens"][:idx])
    assert choice["text"] == want_text
    assert out["usage"]["completion_tokens"] == idx + 1

    # streaming path: the stop token's chunk is skipped too
    resp = post(server, "/v1/completions",
                {"prompt": [5, 7, 11], "max_tokens": 6, "stop_token": stop,
                 "stream": True}, raw=True)
    events = [l.strip()[6:] for l in resp if l.strip().startswith(b"data: ")]
    assert events[-1] == b"[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
    texts = [c["choices"][0]["text"] for c in chunks[:-1]]
    assert "".join(texts) == want_text


# -- stop sequences ---------------------------------------------------------

def test_stop_matcher_unit():
    from butterfly_tpu.serve.server import StopSequenceMatcher
    m = StopSequenceMatcher(["END"])
    assert m.feed("hello ") == "hello "
    assert m.feed("E") == ""          # holdback: could grow into END
    assert m.feed("x") == "Ex"        # not a stop after all
    assert m.feed("EN") == ""
    assert m.feed("D ignored") == ""  # hit: nothing past the stop leaks
    assert m.hit
    assert m.text[:m.released] == "hello Ex"

    m2 = StopSequenceMatcher(["ab", "b"])
    assert m2.feed("xa") == "x"       # 'a' held (prefix of 'ab')
    assert m2.feed("b") == ""         # earliest match wins ('ab' at 1)
    assert m2.hit and m2.text[:m2.released] == "x"

    m3 = StopSequenceMatcher(["zz"])
    assert m3.feed("az") == "a"
    assert m3.flush() == "z"          # no hit: holdback released


def _pieces(tokens):
    return [ByteTokenizer().decode([t]) for t in tokens]


def test_openai_completions_stop_sequence_blocking(server):
    ref = post(server, "/generate",
               {"tokens": [5, 7, 11], "max_tokens": 6, "stop_token": -1})
    pieces = _pieces(ref["tokens"])
    full = "".join(pieces)
    stop = pieces[2] + pieces[3]
    out = post(server, "/v1/completions",
               {"prompt": [5, 7, 11], "max_tokens": 6, "stop_token": -1,
                "stop": stop})
    (choice,) = out["choices"]
    assert choice["finish_reason"] == "stop"
    assert choice["text"] == full[:full.find(stop)]


def test_openai_completions_stop_sequence_stream(server):
    ref = post(server, "/generate",
               {"tokens": [5, 7, 11], "max_tokens": 6, "stop_token": -1})
    pieces = _pieces(ref["tokens"])
    full = "".join(pieces)
    stop = pieces[2] + pieces[3]
    resp = post(server, "/v1/completions",
                {"prompt": [5, 7, 11], "max_tokens": 6, "stop_token": -1,
                 "stop": [stop], "stream": True}, raw=True)
    events = [l.strip()[6:] for l in resp if l.strip().startswith(b"data: ")]
    assert events[-1] == b"[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
    streamed = "".join(c["choices"][0]["text"] for c in chunks)
    assert streamed == full[:full.find(stop)]


def test_openai_completions_invalid_stop_is_400(server):
    import urllib.error
    for bad in ({"stop": 7}, {"stop": ["a", "b", "c", "d", "e"]},
                {"stop": [1, 2]}):
        with pytest.raises(urllib.error.HTTPError) as e:
            post(server, "/v1/completions",
                 {"prompt": "hi", "max_tokens": 2, **bad})
        assert e.value.code == 400


def test_openai_error_envelope_from_admit_path(server):
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/v1/completions",
             {"prompt": [999999], "max_tokens": 2})
    assert e.value.code == 400
    body = json.loads(e.value.read())
    assert body["error"]["type"] == "invalid_request_error"
    assert "out of range" in body["error"]["message"]
    # native endpoint keeps the flat shape
    with pytest.raises(urllib.error.HTTPError) as e2:
        post(server, "/generate", {"tokens": [999999], "max_tokens": 2})
    assert json.loads(e2.value.read())["error"] == "token id out of range"


# ---------------------------------------------------------------------------
# overload protection (ISSUE 8): deadline 504s, priorities, lock timeouts
# ---------------------------------------------------------------------------

def test_spent_deadline_is_504_at_admission(server):
    """A request arriving with its budget already spent gets a terminal
    504 with where/elapsed detail — it never touches the queue."""
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/generate",
             {"tokens": [1, 2], "max_tokens": 2, "deadline_ms": 0})
    assert e.value.code == 504
    body = json.loads(e.value.read())
    assert body["error"] == "deadline exceeded"
    assert body["where"] == "admission"
    assert "elapsed_ms" in body and body["deadline_ms"] == 0
    # header form (X-Deadline-Ms) wins and takes the same path; the
    # OpenAI endpoint answers in its error envelope
    req = urllib.request.Request(
        server + "/v1/completions",
        data=json.dumps({"prompt": "hi", "max_tokens": 2}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Deadline-Ms": "-5"})
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 504
    env = json.loads(e.value.read())["error"]
    assert env["type"] == "timeout_error" and env["where"] == "admission"
    # counted (handler-side: the scheduler never saw the request)
    text = get(server, "/metrics")
    assert 'butterfly_deadline_expired_total{where="admission"}' in text


def test_generous_deadline_serves_normally(server):
    out = post(server, "/generate",
               {"tokens": [5, 7], "max_tokens": 3, "stop_token": -1,
                "deadline_ms": 120_000, "priority": "batch"})
    assert len(out["tokens"]) == 3


def test_unknown_priority_is_400(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/generate",
             {"tokens": [1], "max_tokens": 2, "priority": "urgent"})
    assert e.value.code == 400
    assert "priority" in json.loads(e.value.read())["error"]


def test_lock_timeout_answers_503_with_retry_after():
    """A held serving lock (slow/hung tick) must not pin handler
    threads: bounded acquire, 503 + Retry-After, and the timeout is
    counted. Uses a local server whose lock the test holds."""
    from http.server import ThreadingHTTPServer
    model = Model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    rt = RuntimeConfig(max_batch_size=1, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(model, params, rt))
    state = ServerState(sched, ByteTokenizer())
    # scheduler loop deliberately NOT started: the lock stays ours.
    # Admission tolerates compile-length waits in production (30s);
    # shrink it so the test observes the timeout without the wait.
    state.submit_lock_timeout = 0.5
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_port}"
    state.lock.acquire()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/metrics", timeout=30)
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") == "1"
        with pytest.raises(urllib.error.HTTPError) as e:
            post(url, "/generate", {"tokens": [1], "max_tokens": 2})
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After") == "1"
        assert sched.registry.get(
            "server_lock_timeouts_total").value == 2
    finally:
        state.lock.release()
        httpd.shutdown()
        httpd.server_close()
    # with the lock free again the same surfaces answer normally
    # (no scheduler thread ran: only the lock-free paths are probed)
    assert "butterfly_server_lock_timeouts_total 2" \
        in state.metrics_text()


# ---------------------------------------------------------------------------
# tick anatomy endpoints: /debug/ticks, /debug/flightrecorder,
# /debug/profile (ISSUE 15)
# ---------------------------------------------------------------------------

def test_debug_ticks_endpoint(server):
    post(server, "/generate",
         {"tokens": [5, 7, 11], "max_tokens": 4, "stop_token": -1})
    body = json.loads(get(server, "/debug/ticks"))
    assert body["enabled"] is True
    assert body["ticks"], "the generate above must have ticked"
    t = body["ticks"][-1]
    for key in ("seq", "wall_s", "phases", "fetch_s", "inflight",
                "barrier_causes", "batch", "waiting", "pages_free"):
        assert key in t, key
    # phase sums reconcile with tick wall (the ring serves exactly what
    # tools/tick_report.py renders)
    assert abs(sum(t["phases"].values()) - t["wall_s"]) \
        <= 0.1 * t["wall_s"] + 1e-6
    # ?n=K limits the window
    limited = json.loads(get(server, "/debug/ticks?n=1"))
    assert len(limited["ticks"]) == 1


def test_debug_flightrecorder_endpoint(server):
    post(server, "/generate",
         {"tokens": [5, 7], "max_tokens": 3, "stop_token": -1})
    body = json.loads(get(server, "/debug/flightrecorder"))
    assert body["enabled"] is True
    kinds = {e["kind"] for e in body["events"]}
    assert "admit" in kinds  # the admissions above were recorded
    assert body["dumps"] == []  # nothing anomalous happened


def test_debug_profile_no_xprof_501(server, monkeypatch):
    """The graceful no-xprof fallback: a capture whose start fails
    (profiler plugin absent) answers 501 with the reason — never a
    crash, never a held serving lock."""
    from butterfly_tpu.serve.server import ServerState

    def boom(logdir):
        raise ImportError("no xprof in this build")

    monkeypatch.setattr(ServerState, "_profiler_start",
                        staticmethod(boom))
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server, "/debug/profile", {"duration_ms": 50})
    assert e.value.code == 501
    body = json.loads(e.value.read())
    assert "no xprof" in body["error"]
    # the server is still fully alive after the failed capture
    out = post(server, "/generate",
               {"tokens": [5, 7], "max_tokens": 2, "stop_token": -1})
    assert len(out["tokens"]) == 2


def test_debug_profile_live_capture_never_blocks_admission(server):
    """POST /debug/profile on a live replica: the capture brackets the
    tick loop WITHOUT the serving lock, so a /generate submitted
    mid-capture is admitted and completes while the capture is still
    open. Returns a capture artifact (or a clean 501 where xprof is
    genuinely absent)."""
    import threading
    result = {}
    # warm the exact serving programs first so the mid-capture latency
    # below measures admission, not a first-shape XLA compile
    post(server, "/generate",
         {"tokens": [5, 7, 11], "max_tokens": 4, "stop_token": -1})

    def capture():
        try:
            result["resp"] = post(server, "/debug/profile",
                                  {"duration_ms": 8000})
            result["code"] = 200
        except urllib.error.HTTPError as e:
            result["code"] = e.code
            result["resp"] = json.loads(e.read())

    t = threading.Thread(target=capture)
    t.start()
    # mid-capture traffic: admitted, decoded, and answered while the
    # capture thread is STILL blocked on its 8s window — the direct
    # proof the capture holds no serving lock (profiling slows the CPU
    # backend, so a wall-clock bound would flake; liveness of the
    # capture thread is the non-racy signal)
    out = post(server, "/generate",
               {"tokens": [5, 7, 11], "max_tokens": 4, "stop_token": -1})
    assert len(out["tokens"]) == 4
    still_capturing = t.is_alive()
    t.join(timeout=60)
    assert not t.is_alive()
    assert result["code"] in (200, 501), result
    if result["code"] == 200:
        assert still_capturing, \
            "the generate should have finished inside the capture window"
        body = result["resp"]
        assert body["files"], "a capture must produce artifact files"
        assert body["duration_ms"] == 8000
    # second capture works too (the guard releases)
    try:
        post(server, "/debug/profile", {"duration_ms": 50})
    except urllib.error.HTTPError as e:
        assert e.code == 501


def test_profile_path_never_touches_serving_lock():
    """The BTF004-shaped pin, direct: the capture code path must not
    reference the serving lock at all — bounded-acquire-to-flip-a-flag
    is the contract, and here the flag needs no serving lock."""
    import inspect
    from butterfly_tpu.serve.server import ServerState
    for fn in (ServerState._maybe_profile, ServerState._profile_export,
               ServerState.request_profile):
        src = inspect.getsource(fn)
        assert "self.lock" not in src
        assert "acquire_lock" not in src


def test_profiler_server_start_guarded():
    """`serve --profiler-port` small fix: start succeeds at most once
    per process and every failure (second start, port in use) is a
    logged False, never a crash."""
    from butterfly_tpu.obs.profile import start_profiler_server
    first = start_profiler_server(49741)
    second = start_profiler_server(49741)
    assert isinstance(first, bool) and isinstance(second, bool)
    # whatever the environment supports, a repeat start must degrade
    assert second is False


# ---------------------------------------------------------------------------
# the tick on the profiler's clock (ISSUE 24): bf. spans in a capture,
# the export off the loop thread, the lock wait on the submit event
# ---------------------------------------------------------------------------

import contextlib  # noqa: E402


@contextlib.contextmanager
def _serving(**rt_kw):
    """A server of its own: (url, state)."""
    from http.server import ThreadingHTTPServer
    from butterfly_tpu.obs.trace import Tracer
    model = Model(CFG)
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                       **rt_kw)
    sched = Scheduler(ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                                    rt), tracer=Tracer())
    state = ServerState(sched, ByteTokenizer())
    state.thread.start()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        yield f"http://127.0.0.1:{httpd.server_port}", state
    finally:
        state.stop.set()
        httpd.shutdown()
        state.thread.join(timeout=30)


def _capture(url, tmp_path, duration_ms):
    """POST /debug/profile under steady traffic. Returns the answer, the
    wall time it came, the capture's bf. events [(name, stats)] and
    every tick record (followed as the traffic goes: the ring is short).
    """
    from jax.profiler import ProfileData
    body = {"tokens": [5, 7, 11], "max_tokens": 24, "stop_token": -1}
    for _ in range(3):      # compile outside the capture: a request
        post(url, "/generate", body)    # into a used slot has programs
                                        # of its own
    stop = threading.Event()
    ticks, since = [], 0

    def traffic():
        nonlocal since
        while not stop.is_set():
            post(url, "/generate", body)
            d = json.loads(get(url, f"/debug/ticks?since={since}"))
            ticks.extend(d["ticks"])
            since = d["next_seq"]

    t = threading.Thread(target=traffic)
    t.start()
    try:
        resp = post(url, "/debug/profile",
                    {"duration_ms": duration_ms, "logdir": str(tmp_path)})
        t_resp = time.time()
    finally:
        stop.set()
        t.join(timeout=120)
    assert not t.is_alive()
    files = [f for f in resp["files"] if f.endswith(".xplane.pb")]
    assert files, resp
    data = ProfileData.from_file(str(tmp_path / files[-1]))
    events = [(e.name, dict(e.stats)) for p in data.planes
              for ln in p.lines for e in ln.events
              if e.name.startswith("bf.")]
    return resp, t_resp, events, ticks


def test_capture_holds_the_ticks_spans_and_exports_off_the_loop(tmp_path):
    """Every phase of the mixed path and every sub-span is a `bf.` event
    of the capture; `bf.tick` carries the tick record's `seq`; the
    handler answers 200 once the export is done, and the loop went on
    ticking while it ran."""
    with _serving() as (url, state):
        resp, t_resp, events, ticks = _capture(url, tmp_path, 2500)
    names = {n for n, _ in events}
    assert {"bf.tick", "bf.loop.lock", "bf.loop.wait",
            "bf.tick.drain.fetch", "bf.tick.drain.emit",
            "bf.tick.dispatch.put", "bf.tick.dispatch.launch"} <= names
    assert {"bf.tick." + p for p in (
        "expire", "drain_oldest", "drain_barrier", "admit", "assemble",
        "mixed", "flush")} <= names
    seqs = [st["seq"] for n, st in events if n == "bf.tick"]
    assert seqs and all(isinstance(s, int) for s in seqs)
    # the join with /debug/ticks
    assert set(seqs) <= {t["seq"] for t in ticks}
    by_seq = {t["seq"]: t for t in ticks}
    launches = [st for n, st in events if n == "bf.tick.dispatch.launch"]
    assert {st["program"] for st in launches} <= {
        "bf_mixed_block_win", "bf_decode_block_win"}
    assert {st["block"] for st in launches} & {t["block"] for t in ticks}
    # the launch that ended a wait of the device says how long it was,
    # and the tick record of the same block holds the same number
    assert all(st["starved_ms"] >= 0.0 for st in launches)
    by_block = {t["block"]: t for t in ticks if t["program"]}
    joined = [(st, by_block[st["block"]]) for st in launches
              if st["block"] in by_block]
    assert joined
    for st, t in joined:
        assert st["starved_ms"] == pytest.approx(1e3 * t["starved_s"],
                                                 abs=1e-3)
    assert "bf.tick.admit.seed" in names
    # the ticks of the capture say so, the ones before and after do not
    # (the capture is stopped on a thread of its own: the first ticks
    # after the flag fell may still be in it)
    assert {t["profiled"] for t in ticks} == {True, False}
    flags = [by_seq[s]["profiled"] for s in seqs if s in by_seq]
    assert flags[0] and sum(flags) >= 0.8 * len(flags)
    assert flags == sorted(flags, reverse=True)
    assert all("blocks" in st for n, st in events
               if n == "bf.tick.drain.fetch")
    assert any(st.get("tokens", 0) > 0 for n, st in events
               if n == "bf.tick.drain.emit")
    assert all(by_seq[s]["batch"] <= 2 for s in seqs if s in by_seq)
    # the export ran on its own thread: ticks completed meanwhile
    assert resp["export_s"] > 0 and resp["duration_s"] >= 2.5
    during = [t for t in ticks
              if t_resp - resp["export_s"] < t["t_wall"] < t_resp]
    assert during, (resp, len(ticks))
    assert all("lock_s" in t and t["compiles"] >= 0 for t in ticks)
    # lazy drains say whether the newer block was still running
    assert {t["overlapped"] for t in ticks} <= {None, True, False}
    assert any(t["overlapped"] is not None for t in ticks)


def test_capture_holds_the_spec_phases(tmp_path):
    """`spec_emit` (speculation) is a span too, beside `mixed`; no tick
    of either kind has a `dispatch` span of its own (its put and launch
    are sub-spans of `mixed`)."""
    with _serving(speculative_gamma=2) as (url, _):
        _, _, events, _ = _capture(url, tmp_path / "spec", 1500)
    names = {n for n, _ in events}
    assert {"bf.tick.mixed", "bf.tick.spec_emit", "bf.tick.admit",
            "bf.tick.drain.emit"} <= names
    assert "bf.tick.dispatch" not in names
    assert {st["program"] for n, st in events
            if n == "bf.tick.dispatch.launch"} \
        >= {"bf_mixed_spec_block_win"}


def test_submit_event_carries_the_lock_wait():
    """The handler's wait for the serving lock is on the `submit` event
    (`lock_wait_s`, beside `t_recv`), and in the timeline's summary."""
    from butterfly_tpu.obs.trace import summarize_timeline
    with _serving() as (url, state):
        post(url, "/generate", {"tokens": [5, 7], "max_tokens": 2,
                                "stop_token": -1})
        out = {}
        assert state.lock.acquire(timeout=30)
        try:
            t = threading.Thread(target=lambda: out.update(post(
                url, "/generate", {"tokens": [5, 7, 11], "max_tokens": 2,
                                   "stop_token": -1,
                                   "request_id": "held"})))
            t.start()
            time.sleep(0.4)
        finally:
            state.lock.release()
        t.join(timeout=60)
        assert len(out["tokens"]) == 2
        health = json.loads(get(url, "/health"))
        recs = json.loads(get(url, "/debug/requests"))["requests"]
    rec = next(r for r in recs if r["request_id"] == "held")
    submit = next(e for e in rec["events"] if e["name"] == "submit")
    assert submit["lock_wait_s"] >= 0.3
    assert submit["t_recv"] <= submit["t"] - submit["lock_wait_s"] + 1e-3
    assert summarize_timeline(rec)["lock_wait_s"] == submit["lock_wait_s"]
    other = next(r for r in recs if r["request_id"] != "held")
    assert next(e for e in other["events"]
                if e["name"] == "submit")["lock_wait_s"] < 0.3
    # nothing compiled after the server was built but new shapes
    assert health["compiles_after_ready"] == 0


def test_a_burst_passes_a_ticking_loop_in_the_order_it_came(monkeypatch):
    """The loop hands the lock from one tick to the next without a
    pause: before each tick it lets the handlers that are submitting
    through (bounded), so a burst that meets a busy loop waits a tick
    and not for its luck, and queues in the order it was received."""
    import butterfly_tpu.serve.server as srv
    monkeypatch.setattr(srv, "SUBMIT_GRACE_S", 5.0)  # a loaded test host
    with _serving() as (url, state):
        post(url, "/generate", {"tokens": [5, 7, 11], "max_tokens": 8,
                                "stop_token": -1})      # programs compiled
        sched = state.sched
        tick, seen = sched.tick, []

        def slow_tick():            # a block's wait for the device
            seen.append([r.client_id for r in sched.waiting])
            time.sleep(0.05)
            return tick()
        sched.tick = slow_tick
        first = state.submit([5, 7], 40, 0.0, -1, request_id="long")[0]
        while len(seen) < 3:        # the loop is ticking
            time.sleep(0.005)
        burst = [threading.Thread(target=state.submit, args=(
            [5, 7, 11], 2, 0.0, -1), kwargs={
            "request_id": f"b{i}", "t_recv": 100.0 - i})
            for i in range(16)]
        n0 = len(seen)
        for t in burst:
            t.start()
        for t in burst:
            t.join(timeout=30)
        ticks_waited = len(seen) - n0
        while not first.done:
            time.sleep(0.01)
        recs = json.loads(get(url, "/debug/requests"))["requests"]
    waits = [next(e for e in r["events"] if e["name"] == "submit")
             ["lock_wait_s"] for r in recs
             if (r["request_id"] or "").startswith("b")]
    assert len(waits) == 16 and max(waits) < 0.5, waits
    assert ticks_waited <= 3
    # received last first (t_recv falls with i): queued so
    queued = next(q for q in seen if len([c for c in q if c != "long"]) == 16)
    assert [c for c in queued if c != "long"] == \
        [f"b{i}" for i in reversed(range(16))]


@pytest.mark.parametrize("grace_s, held_s, ticks_while_held", [
    (5.0, 0.3, False),      # the loop waits for a submission under way
    (0.05, 0.6, True),      # but not for ever: arrivals without end
])
def test_the_loop_lets_submissions_through_before_a_tick(
        monkeypatch, grace_s, held_s, ticks_while_held):
    import butterfly_tpu.serve.server as srv
    monkeypatch.setattr(srv, "SUBMIT_GRACE_S", grace_s)
    model = Model(CFG)
    rt = RuntimeConfig(max_batch_size=1, max_seq_len=64, page_size=8)
    sched = Scheduler(ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                                    rt))
    state = ServerState(sched, ByteTokenizer())
    ticked = threading.Event()
    sched.tick = lambda: ticked.set() or 0
    sched.submit([5, 7], max_new_tokens=2)      # has_work
    try:
        with state._submitting():
            state.thread.start()
            assert ticked.wait(timeout=held_s) == ticks_while_held
        assert ticked.wait(timeout=10)
    finally:
        state.stop.set()
        state.thread.join(timeout=10)
