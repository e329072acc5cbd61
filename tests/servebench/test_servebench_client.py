"""The SSE client against a stand-in server: tokens, [DONE], refusals,
and a 503 that asks for a retry."""
import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import client as C  # noqa: E402
from servebench.traffic import Plan, Request  # noqa: E402


class Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    busy_once = set()       # request ids refused with 503 the first time

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        rid = self.headers["X-Request-Id"]
        if rid in self.busy_once:
            self.busy_once.discard(rid)
            self._plain(503, {"error": "serving lock busy"}, {"Retry-After": "1"})
            return
        if rid.endswith("full"):
            self._plain(429, {"error": "queue full"}, {})
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data):
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
        n = body["max_tokens"]
        for i in range(0, n, 2):          # two tokens to a burst
            for j in range(i, min(i + 2, n)):
                chunk(f'data: {json.dumps({"token": 100 + j, "text": "x"})}\n\n'
                      .encode())
            self.wfile.flush()
            time.sleep(0.02)
        if rid.endswith("broken"):
            chunk(f'data: {json.dumps({"error": "generation aborted"})}\n\n'.encode())
        else:
            chunk(b"data: [DONE]\n\n")
        chunk(b"")

    def _plain(self, code, obj, headers):
        data = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Length", str(len(data)))
        for k, v in headers.items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)


@pytest.fixture()
def port():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def req(rid, n, **kw):
    return Request(rid=rid, tokens=[1, 2, 3], max_tokens=n, **kw)


def drive(port, plan, seconds=3.0):
    gen = C.LoadGenerator("127.0.0.1", port, plan)
    gen.start()
    gen.run(until=time.monotonic() + seconds,
            done=lambda: gen.streams and not gen.live and not gen._retry
            and gen._sched_i >= len(plan.schedule))
    streams = gen.streams
    gen.stop()
    return gen, {s.rid: s for s in streams}


def test_stream_is_timed_token_by_token(port):
    plan = Plan(kind="burst", lead_s=0, grace_s=0, queues=[[req("a", 6, client=0)]])
    _, st = drive(port, plan)
    s = st["a"]
    assert s.finished and not s.failed and s.tokens == [100 + i for i in range(6)]
    assert len(s.times) == 6 and s.times == sorted(s.times)
    assert s.times[-1] - s.times[0] > 0.03 and s.end >= s.times[-1]
    assert s.sent is not None and s.due is None


def test_closed_loop_sends_the_next_when_the_last_finished(port):
    plan = Plan(kind="closed", lead_s=0, grace_s=0,
                queues=[[req("c0-0", 2, client=0), req("c0-1", 2, client=0)],
                        [req("c1-0", 4, client=1)]])
    gen, st = drive(port, plan)
    assert set(st) == {"c0-0", "c0-1", "c1-0"} and all(s.finished for s in st.values())
    assert st["c0-1"].sent >= st["c0-0"].end
    assert gen.exhausted          # both clients ran out of planned requests


def test_open_loop_sends_when_due_and_notes_lateness(port):
    plan = Plan(kind="open", lead_s=0, grace_s=0,
                schedule=[req("o0", 2, due=0.05), req("o1", 2, due=0.3)])
    gen, st = drive(port, plan)
    for rid, due in (("o0", 0.05), ("o1", 0.3)):
        s = st[rid]
        assert s.finished and s.due == pytest.approx(gen.t_zero + due)
        assert 0 <= s.sent - s.due < 0.1


def test_a_503_with_retry_after_is_sent_again(port, monkeypatch):
    monkeypatch.setattr(C, "RETRY_AFTER_S", 0.1)
    Handler.busy_once.add("busy")
    plan = Plan(kind="open", lead_s=0, grace_s=0,
                schedule=[req("busy", 4, due=0.0)])
    _, st = drive(port, plan)
    s = st["busy"]
    assert s.retries == 1 and s.finished and not s.failed and len(s.tokens) == 4
    assert len(st) == 1                 # the same request, not a new one
    assert s.times[0] - s.due >= 0.1    # the wait shows in its time to first token


def test_refusal_and_error_event_are_failures(port):
    plan = Plan(kind="burst", lead_s=0, grace_s=0,
                queues=[[req("q-full", 4, client=0)], [req("s-broken", 2, client=1)]])
    _, st = drive(port, plan)
    assert st["q-full"].failed.startswith("refused: HTTP/1.1 429")
    assert not st["q-full"].times and not st["q-full"].finished
    assert "generation aborted" in st["s-broken"].failed
    assert len(st["s-broken"].tokens) == 2 and not st["s-broken"].finished
