"""The load generator and its SSE clients: one thread, one selector.

Every stream is a non-blocking socket on one `selectors` loop, so the
generator is one thread of one process that never touches JAX: it shares
no interpreter lock with the server's tick loop. A token's time is
`time.monotonic()` when the `recv` that carried it returned.

Open loop: each request is sent when it is due by the plan, whatever the
server is doing; `sent - due` is how late the generator ran. Closed
loop: each client sends its next request when its last one finished.

stdlib only.
"""
from __future__ import annotations

import json
import re
import selectors
import socket
import time
from typing import Callable, Dict, List, Optional

from servebench.metrics import Stream
from servebench.traffic import Plan, Request

#: the server's `Retry-After: 1`
RETRY_AFTER_S = 1.0
_EVENT = re.compile(rb"data: (.*?)\n\n", re.S)
_TOKEN = re.compile(rb'"token": (-?\d+)')


class _Conn:
    def __init__(self, sock, stream: Stream, req: Request):
        self.sock, self.stream, self.req = sock, stream, req
        self.buf = b""
        self.headers_done = False


class LoadGenerator:
    """Drives one plan against `host:port` and records what every
    client saw. `run(until)` returns at `until()`'s say-so."""

    def __init__(self, host: str, port: int, plan: Plan):
        self.host, self.port, self.plan = host, port, plan
        self.sel = selectors.DefaultSelector()
        self.streams: List[Stream] = []
        self.live: Dict[int, _Conn] = {}
        self._retry: list = []                  # (when, request, stream)
        self._next = [0] * len(plan.queues)     # closed loop cursors
        self._sched_i = 0                       # open loop cursor
        self.t_zero: Optional[float] = None     # schedule zero (open)

    # -- sending -----------------------------------------------------------

    def _send(self, req: Request, due: Optional[float],
              st: Optional[Stream] = None) -> None:
        if st is None:
            st = Stream(rid=req.rid, prompt_len=len(req.tokens),
                        asked=req.max_tokens, phase=req.phase, due=due)
            self.streams.append(st)
        body = json.dumps({"tokens": req.tokens, "max_tokens": req.max_tokens,
                           "temperature": 0.0, "stop_token": -1,
                           "stream": True}).encode()
        head = (f"POST /generate HTTP/1.1\r\nHost: {self.host}\r\n"
                f"Content-Type: application/json\r\n"
                f"X-Request-Id: {req.rid}\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        try:
            sock = socket.create_connection((self.host, self.port), timeout=10)
            sock.sendall(head + body)
            sock.setblocking(False)
        except OSError as e:
            st.sent = st.sent or time.monotonic()
            self._fail(st, f"send: {type(e).__name__}: {e}", req)
            return
        st.sent = st.sent or time.monotonic()
        conn = _Conn(sock, st, req)
        self.live[sock.fileno()] = conn
        self.sel.register(sock, selectors.EVENT_READ, conn)

    def _fail(self, st: Stream, why: str, req: Request) -> None:
        st.failed, st.end = why, time.monotonic()
        self._after(req)

    def _after(self, req: Request) -> None:
        """A closed-loop client sends its next request at once."""
        if self.plan.kind == "closed" and not self._stopping:
            self._send_next(req.client)

    def _send_next(self, client: int) -> None:
        q = self.plan.queues[client]
        i = self._next[client]
        if i < len(q):
            self._next[client] = i + 1
            self._send(q[i], None)
        elif self.plan.kind == "closed":
            self.exhausted = True

    # -- receiving ---------------------------------------------------------

    def _close(self, conn: _Conn) -> None:
        try:
            self.sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        self.live.pop(conn.sock.fileno(), None)
        conn.sock.close()

    def _on_read(self, conn: _Conn) -> None:
        st = conn.stream
        try:
            data = conn.sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError as e:
            self._close(conn)
            self._fail(st, f"recv: {type(e).__name__}: {e}", conn.req)
            return
        now = time.monotonic()
        if not data:
            self._close(conn)
            if not st.finished and not st.failed:
                self._fail(st, "stream closed before [DONE]", conn.req)
            return
        conn.buf += data
        if not conn.headers_done:
            end = conn.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            status = conn.buf[:end].split(b"\r\n", 1)[0]
            if b" 503" in status and b"retry-after" in conn.buf[:end].lower() \
                    and not self._stopping:
                # the server asks for a retry (its serving lock was busy):
                # a client does as it is told; the request stays due when
                # it was, so the wait shows in its time to first token
                self._close(conn)
                st.retries += 1
                self._retry.append((now + RETRY_AFTER_S, conn.req, st))
                return
            if b" 200" not in status:
                self._close(conn)
                self._fail(st, "refused: " + status.decode("latin1")
                           + " " + conn.buf[end + 4:][:200].decode("latin1"),
                           conn.req)
                return
            conn.headers_done = True
            conn.buf = conn.buf[end + 4:]
        last = 0
        for m in _EVENT.finditer(conn.buf):
            last = m.end()
            payload = m.group(1)
            tok = _TOKEN.search(payload)
            if tok and payload.startswith(b'{"token"'):
                st.times.append(now)
                st.tokens.append(int(tok.group(1)))
            elif payload == b"[DONE]":
                st.finished, st.end = True, now
            else:
                st.failed, st.end = "event: " + payload[:200].decode(
                    "utf-8", "replace"), now
        conn.buf = conn.buf[last:]
        if st.finished or st.failed:
            self._close(conn)
            self._after(conn.req)

    # -- the loop ----------------------------------------------------------

    _stopping = False
    exhausted = False

    def start(self) -> None:
        """Schedule zero: the closed loop's first wave goes out; the
        open loop's clock starts."""
        self.t_zero = time.monotonic()
        if self.plan.kind in ("closed", "burst"):
            for c in range(len(self.plan.queues)):
                self._send_next(c)

    def run(self, until: float, stop_sending_at: Optional[float] = None,
            done: Optional[Callable[[], bool]] = None) -> None:
        """Serve the loop until the clock reads `until` (or `done()`
        says so); open-loop requests due after `stop_sending_at` are
        never sent."""
        sched = self.plan.schedule
        while True:
            now = time.monotonic()
            if now >= until or (done is not None and done()):
                return
            wait = until - now
            for item in [r for r in self._retry if r[0] <= now]:
                self._retry.remove(item)
                self._send(item[1], item[2].due, item[2])
            if self._retry:
                wait = min(wait, max(0.0, min(r[0] for r in self._retry) - now))
            if self.plan.kind == "open":
                while self._sched_i < len(sched):
                    req = sched[self._sched_i]
                    due = self.t_zero + req.due
                    if stop_sending_at is not None and due >= stop_sending_at:
                        self._sched_i = len(sched)
                        break
                    if due > now:
                        wait = min(wait, due - now)
                        break
                    self._sched_i += 1
                    self._send(req, due)
                    now = time.monotonic()
            for key, _ in self.sel.select(timeout=max(0.0, min(wait, 0.5))):
                self._on_read(key.data)

    def stop(self) -> None:
        """Drop every open stream (the server cancels them)."""
        self._stopping = True
        for conn in list(self.live.values()):
            self._close(conn)
        self.sel.close()
