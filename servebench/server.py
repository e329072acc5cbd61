"""The system under test as a child process, and plain HTTP to it.

stdlib only: the parent never imports JAX, so the child is the one
process that holds the chip.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parent.parent
READY_LINE = "[butterfly] serving "


class ServerFailed(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """`butterfly serve` for one configuration file, through
    servebench/launcher.py. Its output goes to `log`."""

    def __init__(self, config_path: Path, log: Path, env: dict,
                 require_tpu: int = 0):
        self.config_path, self.log, self.env = config_path, log, env
        self.require_tpu = require_tpu
        self.port = free_port()
        self.proc: Optional[subprocess.Popen] = None

    def start(self) -> None:
        cmd = [sys.executable, str(ROOT / "servebench" / "launcher.py"),
               "--config", str(self.config_path), "--port", str(self.port),
               "--require-tpu", str(self.require_tpu)]
        self.log.parent.mkdir(parents=True, exist_ok=True)
        with self.log.open("wb") as out:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=dict(self.env, PYTHONUNBUFFERED="1"),
                stdout=out, stderr=subprocess.STDOUT, start_new_session=True)

    def wait_ready(self, timeout: float) -> str:
        """Until the server prints that it listens; returns that line."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise ServerFailed(f"server exited with code "
                                   f"{self.proc.returncode} before listening\n"
                                   + self.tail())
            text = self.log.read_text(errors="replace")
            if READY_LINE in text:
                # the line is printed just before the socket is bound
                try:
                    self.get("/health", timeout=5)
                except OSError:
                    time.sleep(0.1)
                    continue
                return next(ln for ln in text.splitlines()
                            if ln.startswith(READY_LINE))
            time.sleep(0.25)
        raise ServerFailed(f"server not listening after {timeout:.0f}s\n"
                           + self.tail())

    def tail(self, n: int = 30) -> str:
        try:
            lines = self.log.read_text(errors="replace").splitlines()
        except OSError:
            return ""
        return "\n".join("    | " + ln for ln in lines[-n:])

    def get(self, path: str, timeout: float = 30):
        return self._http(path, None, timeout)

    def post(self, path: str, body: dict, timeout: float = 120):
        return self._http(path, body, timeout)

    def _http(self, path, body, timeout):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data,
            headers={"Content-Type": "application/json"} if data else {})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read().decode("utf-8", "replace")
        ctype = resp.headers.get("Content-Type", "")
        return json.loads(raw) if "json" in ctype else raw

    def wait_idle(self, timeout: float = 30.0) -> None:
        """Until the server has no request left (dropped streams are
        cancelled at their next token). The program's `serve_forever`
        returns without joining its tick thread, so a SIGTERM that lands
        in the middle of a tick can abort the process on its way out
        (seen on the chip and on the CPU, PR 23): stop an idle server."""
        until = time.monotonic() + timeout
        while time.monotonic() < until and self.proc.poll() is None:
            try:
                h = self.get("/health", timeout=5)
            except (OSError, ValueError):
                return
            if not (h.get("active") or h.get("queue_depth")
                    or h.get("inflight_depth")):
                time.sleep(0.3)
                return
            time.sleep(0.1)

    def stop(self, wait: float = 60) -> Optional[int]:
        """SIGTERM to the child's group, then wait for it; SIGKILL if it
        lingers. Returns its exit code (0 = served and was not wedged)."""
        proc = self.proc
        if proc is None:
            return None
        if proc.poll() is None:
            self.wait_idle()
            try:
                os.killpg(proc.pid, signal.SIGTERM)
                proc.wait(timeout=wait)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=30)
        else:
            try:   # whatever the child left in its group
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        return proc.returncode
