"""The process's side of the profiler and of the compiler's own events.

`start_profiler_server()` lets TensorBoard/XProf capture from a live
serving process (`serve --profiler-port`); `POST /debug/profile`
(serve/server.py) captures without it. `count_compiles()` counts what
the process compiles, from JAX's own monitoring events: a compilation
inside a tick stalls every stream, and only the process sees one that
the persistent cache answered or that ran with the cache off.
"""
from __future__ import annotations

import sys
import threading

#: the live ProfilerServer (jax returns a handle that must stay
#: referenced; dropping it would stop the server)
_PROFILER_SERVER = None

#: jax.monitoring duration events (jax/_src/dispatch.py): a program
#: built by the backend compiler or fetched from the persistent cache,
#: and the tracing and lowering that come before either
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_AND_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")


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
