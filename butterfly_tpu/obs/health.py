"""Heartbeat / failure detection (SURVEY.md §5 failure-detection row).

The reference planned failure detection but has no implementation
(SURVEY.md §0). Design constraint (serve/server.py's invariant): JAX
runs on exactly ONE host thread — so the monitor is a pure WATCHDOG
that never touches the device. The owning (JAX) thread reports
liveness:

* `beat()` after successful device work (a serving tick), or
* `maybe_probe()` when idle — runs the probe IN the calling thread at
  most once per interval and beats on success.

The watchdog thread only compares wall-clock against the last beat:
if no beat lands within `interval * max_misses` seconds it latches
unhealthy and fires `on_failure` once. That catches HANGS (a stalled
collective stops the beats — the probe never returns, and the watchdog
doesn't care) as well as raising probes (counted as misses by
`check_now`, latching at `max_misses`).

Probes: `device_probe` proves the local chip completes a program;
`all_hosts_probe` psums 1 across every process's devices so a dead
peer host stalls it. Both are jitted once and cached — a heartbeat is
a cached dispatch, not a retrace.

Recovery after the latch is deliberately NOT automatic: a chip that
flapped is not trustworthy; restart serving (checkpoint/resume path).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

_DEVICE_PROBE = None
_HOSTS_PROBE = None  # (fn, ndev) memo


def device_probe() -> bool:
    """Prove the default device still completes a program."""
    import jax
    import jax.numpy as jnp
    global _DEVICE_PROBE
    if _DEVICE_PROBE is None:
        _DEVICE_PROBE = jax.jit(lambda x: (x + 1).sum())
    return bool(_DEVICE_PROBE(jnp.ones((8,))) == 16.0)


def all_hosts_probe() -> bool:
    """Prove every process in the job still participates in collectives.

    psum(1) over all devices. This IS a collective: every process must
    invoke it at the same point in its program stream, so it belongs in
    COORDINATED contexts (startup bringup checks, synchronized drain
    points, test harnesses) — never in per-host idle timers, where
    unsynchronized issue order would desync the SPMD stream and wedge
    the job (the serving loop uses device_probe per host instead; a
    dead peer surfaces as the next tick stalling -> staleness latch).
    Single-process: equivalent to device_probe.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    global _HOSTS_PROBE
    ndev = jax.device_count()
    if _HOSTS_PROBE is None or _HOSTS_PROBE[1] != ndev:
        mesh = Mesh(np.asarray(jax.devices()), ("all",))
        fn = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, "all"), mesh=mesh,
            in_specs=P("all"), out_specs=P(), check_vma=False))
        _HOSTS_PROBE = (fn, ndev, mesh)
    fn, _, mesh = _HOSTS_PROBE
    # each process contributes its local shards (a host-local array
    # cannot be implicitly resharded onto a multi-process mesh)
    garr = jax.make_array_from_single_device_arrays(
        (ndev,), NamedSharding(mesh, P("all")),
        [jax.device_put(jnp.ones((1,)), d) for d in mesh.local_devices])
    return int(np.asarray(fn(garr))[0]) == ndev


class HeartbeatMonitor:
    """Watchdog over a liveness timestamp + in-caller-thread probes."""

    def __init__(self, probe: Optional[Callable[[], bool]] = None,
                 interval: float = 10.0, max_misses: int = 6,
                 on_failure: Optional[Callable[[Exception], None]] = None):
        # Default timeout 60s: must exceed any legitimate beat gap. The
        # serving layer warms its programs before starting the monitor,
        # but an uncommon prompt-length bucket can still trigger a
        # mid-tick XLA compile of tens of seconds on a large model —
        # that must read as slow, not dead.
        self.probe = probe or device_probe
        self.interval = interval
        self.max_misses = max_misses
        self.on_failure = on_failure
        self.misses = 0
        self.beats = 0
        self.last_error: str = ""
        self._failed = False
        self._latch_lock = threading.Lock()  # owner + watchdog race
        self._last_beat = time.monotonic()
        # -inf, not 0.0: monotonic() is time-since-boot, so on a freshly
        # booted host 0.0 can be within `interval` of now and the first
        # maybe_probe() would silently skip.
        self._last_probe = float("-inf")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watchdog, daemon=True)

    @property
    def healthy(self) -> bool:
        return not self._failed

    @property
    def timeout(self) -> float:
        return self.interval * self.max_misses

    # -- owner (JAX) thread API ---------------------------------------------

    def beat(self) -> None:
        """Record liveness (call after successful device work)."""
        self._last_beat = time.monotonic()
        self.misses = 0
        self.beats += 1

    def check_now(self) -> bool:
        """Run the probe in THIS thread; beat on success, miss on
        failure (latching at max_misses — raising probes fail faster
        than the staleness timeout)."""
        try:
            ok = bool(self.probe())
            err: Optional[Exception] = None if ok else RuntimeError(
                "heartbeat probe returned falsy")
        except Exception as e:  # noqa: BLE001 — any probe failure counts
            ok, err = False, e
        self._last_probe = time.monotonic()
        if ok:
            self.beat()
            return True
        self.misses += 1
        self.last_error = f"{type(err).__name__}: {err}"
        if self.misses >= self.max_misses:
            self._latch(err)
        return False

    def maybe_probe(self) -> None:
        """check_now() at most once per interval (idle-loop cadence)."""
        if time.monotonic() - self._last_probe >= self.interval:
            self.check_now()

    # -- watchdog thread -----------------------------------------------------

    def start(self) -> "HeartbeatMonitor":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=self.interval + 1.0)

    def _latch(self, err: Optional[Exception]) -> None:
        # one-shot across BOTH callers (owner thread at max_misses and
        # the watchdog on staleness): check-and-set under a lock so a
        # chained alerting hook can never double-fire
        with self._latch_lock:
            if self._failed:
                return
            self._failed = True
        if self.on_failure is not None:
            try:
                self.on_failure(err)
            except Exception:
                pass

    def _watchdog(self) -> None:
        # pure wall-clock staleness check: no JAX from this thread
        while not self._stop.wait(self.interval):
            stale = time.monotonic() - self._last_beat
            if stale > self.timeout and not self._failed:
                self.last_error = (f"no heartbeat for {stale:.1f}s "
                                   f"(timeout {self.timeout:.1f}s)")
                self._latch(RuntimeError(self.last_error))
