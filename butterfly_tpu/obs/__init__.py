"""Observability: metrics exposition, typed instruments, tracing, health.

Only the stdlib-light modules are re-exported here (registry, trace,
metrics); profile/health import jax and stay lazy.
"""
from butterfly_tpu.obs.metrics import (  # noqa: F401
    ThroughputWindow,
    render_prometheus,
)
from butterfly_tpu.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from butterfly_tpu.obs.ticklog import (  # noqa: F401
    FlightRecorder,
    TickLog,
)
from butterfly_tpu.obs.trace import Tracer, summarize_timeline  # noqa: F401
