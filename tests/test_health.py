"""Heartbeat / failure-detection tests (SURVEY.md §5 failure-detection).

Monitor semantics (miss counting, failure latch, one-shot callback,
no-recovery-after-latch), the real device/all-hosts probes on the fake
CPU backend, and the serving integration: a failing heartbeat wedges
the server (503 /health, queued work drained host-side).
"""
import json
import threading
import urllib.error
import urllib.request

import jax
import pytest

from butterfly_tpu.obs.health import (
    HeartbeatMonitor, all_hosts_probe, device_probe)


def test_probes_pass_on_live_backend():
    assert device_probe()
    assert all_hosts_probe()  # psum over all 8 fake devices


def test_monitor_latches_after_max_misses():
    fired = []
    mon = HeartbeatMonitor(probe=lambda: False, max_misses=3,
                           on_failure=lambda e: fired.append(e))
    assert mon.check_now() is False and mon.healthy      # miss 1
    assert mon.check_now() is False and mon.healthy      # miss 2
    assert mon.check_now() is False and not mon.healthy  # miss 3: latch
    assert len(fired) == 1
    mon.check_now()                                      # miss 4
    assert len(fired) == 1                               # callback fired once


def test_monitor_miss_reset_but_latch_sticks():
    calls = iter([False, False, True, False, False, False])
    mon = HeartbeatMonitor(probe=lambda: next(calls), max_misses=3)
    mon.check_now(), mon.check_now()
    assert mon.misses == 2 and mon.healthy
    assert mon.check_now() is True and mon.misses == 0   # recovery resets
    for _ in range(3):
        mon.check_now()
    assert not mon.healthy                               # latched now
    assert mon.beats == 1


def test_monitor_probe_exception_counts_as_miss():
    def boom():
        raise RuntimeError("chip fell over")
    mon = HeartbeatMonitor(probe=boom, max_misses=1)
    assert mon.check_now() is False
    assert not mon.healthy
    assert "chip fell over" in mon.last_error


def test_watchdog_latches_on_stale_beats():
    """The watchdog thread latches purely on wall-clock staleness — it
    detects a HUNG owner (no beats) without ever running the probe."""
    mon = HeartbeatMonitor(interval=0.02, max_misses=2).start()
    try:
        waiter = threading.Event()
        for _ in range(300):
            if not mon.healthy:
                break
            waiter.wait(0.01)
        assert not mon.healthy
        assert "no heartbeat" in mon.last_error
    finally:
        mon.stop()


def test_watchdog_stays_healthy_while_beating():
    mon = HeartbeatMonitor(interval=0.02, max_misses=2).start()
    try:
        waiter = threading.Event()
        for _ in range(20):
            mon.beat()
            waiter.wait(0.01)
        assert mon.healthy
    finally:
        mon.stop()


def test_maybe_probe_respects_interval():
    calls = []
    mon = HeartbeatMonitor(probe=lambda: calls.append(1) or True,
                           interval=3600)
    mon.maybe_probe()
    mon.maybe_probe()  # within the interval: no second probe
    assert len(calls) == 1 and mon.beats == 1


def test_heartbeat_failure_wedges_server():
    """Injected failing heartbeat: /health goes 503, /generate refuses,
    queued requests are drained via the host-only abort path."""
    from http.server import ThreadingHTTPServer
    from butterfly_tpu.core.config import RuntimeConfig, tiny
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler
    from butterfly_tpu.serve.server import ServerState, make_handler
    from butterfly_tpu.utils.tokenizer import ByteTokenizer

    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    model = Model(cfg)
    sched = Scheduler(ServingEngine(
        model, model.init(jax.random.PRNGKey(0)),
        RuntimeConfig(max_batch_size=2, max_seq_len=64)))
    hb = HeartbeatMonitor(probe=lambda: False, interval=3600,
                          max_misses=1)  # driven manually below
    state = ServerState(sched, ByteTokenizer(), heartbeat=hb)
    # NB: ServerState.start of the monitor thread uses interval=3600, so
    # the failure is triggered deterministically here:
    hb.check_now()
    assert not hb.healthy and state.error.startswith("heartbeat failed")

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/health", timeout=30)
        assert ei.value.code == 503
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"tokens": [1, 2], "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
    finally:
        state.stop.set()
        hb.stop()
        httpd.shutdown()
