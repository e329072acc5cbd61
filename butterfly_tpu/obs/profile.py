"""The process's side of the profiler and of the compiler's own events.

`start_profiler_server()` lets TensorBoard/XProf capture from a live
serving process (`serve --profiler-port`); `POST /debug/profile`
(serve/server.py) captures without it. `count_compiles()` counts what
the process compiles, from JAX's own monitoring events: a compilation
inside a tick stalls every stream, and only the process sees one that
the persistent cache answered or that ran with the cache off.
`count_collections()` counts the interpreter's garbage collections and
the seconds they took, whichever thread ran them: a collection holds
the interpreter lock, so every thread waits through it.
`run_delay_s()` is the calling thread's time RUNNABLE and not running,
by the kernel's own account.
"""
from __future__ import annotations

import gc
import os
import sys
import threading
import time
from typing import Optional

#: the live ProfilerServer (jax returns a handle that must stay
#: referenced; dropping it would stop the server)
_PROFILER_SERVER = None

#: jax.monitoring duration events (jax/_src/dispatch.py): a program
#: built by the backend compiler or fetched from the persistent cache,
#: and the tracing and lowering that come before either
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_AND_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")

#: the kernel's scheduler statistics of ONE thread: nanoseconds on a
#: CPU, nanoseconds runnable and waiting for one, timeslices
_SCHEDSTAT = "/proc/thread-self/schedstat"
#: each thread's open `_SCHEDSTAT` (`file`; None where there is none to
#: open): the path names the thread that OPENS it, so a descriptor is
#: its opener's for good, and goes with it
_sched = threading.local()


def start_profiler_server(port: int = 9999) -> bool:
    """On-demand profiling for live servers (connect with TensorBoard/
    XProf). Returns True when listening. Failure — jax without the
    profiler plugin (ImportError), the port already bound, a second
    start in one process — logs a warning and returns False instead of
    crashing the serve entrypoint (`serve --profiler-port` is an
    observability convenience, never worth taking the replica down)."""
    global _PROFILER_SERVER
    try:
        import jax
        _PROFILER_SERVER = jax.profiler.start_server(port)
        return True
    except ImportError as e:
        print(f"[butterfly] profiler server unavailable (no xprof): {e}",
              file=sys.stderr, flush=True)
        return False
    except Exception as e:  # port in use / double start / backend quirk
        print(f"[butterfly] profiler server failed to start on :{port}: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return False


def count_compiles(registry):
    """Feed `registry`'s `compiles_total` (one per program the backend
    compiled or fetched from the persistent cache) and
    `compile_seconds_total` (tracing, lowering and compiling) from
    JAX's monitoring events, whichever thread compiles. Returns the
    listener, for `jax.monitoring.unregister_event_duration_listener`:
    a process that serves registers once and never removes it."""
    import jax
    n = registry.counter("compiles_total")
    secs = registry.counter("compile_seconds_total")
    lock = threading.Lock()

    def listener(event: str, duration: float, **_) -> None:
        if event == _BACKEND_COMPILE or event in _TRACE_AND_LOWER:
            with lock:
                secs.inc(max(0.0, duration))
                if event == _BACKEND_COMPILE:
                    n.inc()

    jax.monitoring.register_event_duration_secs_listener(listener)
    return listener


def count_collections(registry):
    """Feed `registry`'s `gc_seconds_total` and
    `gc_collections_total{generation}` from the interpreter's
    `gc.callbacks`, whichever thread collects. A collection holds the
    interpreter lock from its `start` to its `stop` and none begins
    inside another, so the callback keeps one start time and takes no
    lock (it may run wherever a thread allocates, under any lock that
    thread holds). Returns the callback, for `gc.callbacks.remove`: a
    process that serves registers once and never removes it."""
    secs = registry.counter("gc_seconds_total")
    by_gen = [registry.counter_family(
        "gc_collections_total", labelnames=("generation",)).labels(str(g))
        for g in range(3)]
    t0 = [0.0]

    def callback(phase: str, info: dict) -> None:
        if phase == "start":
            t0[0] = time.perf_counter()
        elif t0[0]:
            secs.inc(time.perf_counter() - t0[0])
            by_gen[info["generation"]].inc()
            t0[0] = 0.0

    gc.callbacks.append(callback)
    return callback


def run_delay_s() -> Optional[float]:
    """Seconds the CALLING thread has been runnable and not running
    since it started: it had work and no CPU of the machine (the second
    field of its `/proc/thread-self/schedstat`; one `pread` on a
    descriptor the thread opens at its first call). None where the
    kernel keeps no such file."""
    try:
        f = _sched.file
    except AttributeError:
        try:
            f = open(_SCHEDSTAT, "rb", buffering=0)
        except OSError:
            f = None
        _sched.file = f
    if f is None:
        return None
    try:
        return int(os.pread(f.fileno(), 64, 0).split()[1]) * 1e-9
    except (OSError, ValueError, IndexError):
        return None
