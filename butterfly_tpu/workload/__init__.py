"""Workload modeling: stochastic mixed traffic for honest serving numbers.

Every serving number before ISSUE 10 was earned against uniform 128/128
closed-loop traffic (`serving_preemptions: 0` by construction) — chunked
prefill, bucketing, preemption, the prefix cache, and the PR-8 admission
machinery were unmeasured exactly where real traffic hits them.
Production traces show heterogeneous prompt/decode lengths and bursty
arrivals (Patel et al., "Splitwise", arXiv:2311.18677), and
continuous-batching systems are evaluated on length-mixed stochastic
workloads (Kwon et al., vLLM, arXiv:2309.06180). This package is that
substrate:

  models.py    composable request-population specs (length
               distributions, shared-prefix cohorts, priority/deadline
               mix) with seeded, reproducible trace generation
  arrivals.py  open-loop arrival processes (Poisson, bursty Markov-
               modulated on/off, ramp-to-saturation) — load is no
               longer bounded by closed-loop client count
  replay.py    JSONL trace serialization + an absolute-time replay
               driver over a live server/router/control-plane URL
               (reuses tools/loadgen.py's request/judging machinery)

All three are stdlib-only (no jax, no numpy) so traces can be generated
and replayed from any host. The benchmark of record does not use this
package: its traffic is servebench/traffic.py.
"""
from butterfly_tpu.workload.arrivals import (  # noqa: F401
    MarkovOnOff,
    Poisson,
    Ramp,
    assign_arrivals,
    parse_arrival,
)
from butterfly_tpu.workload.models import (  # noqa: F401
    WORKLOADS,
    Cohort,
    LogNormal,
    RequestSpec,
    Uniform,
    Workload,
    get_workload,
    mixed_chat,
)
