"""Continuous-batching scheduler: admission, one fused block a tick,
preemption.

Realizes the reference's planned "Scheduling System" layer
(/root/reference/CLAUDE.md:22 — "Workload distribution and synchronization
across compute nodes"; no implementation exists, SURVEY.md §0) for the
BASELINE.json configs[4] serving shape.

Host-side policy over the static-shape device programs in
engine/serving.py. There is ONE dispatch path:

* tick() = [deadline scrub] then [lazy drain — the OLDEST in-flight
  block only, and only when the in-flight queue is full] then [at most
  one chunk of the seq-parallel long-prompt lane, where a mesh has
  one] then [inline admission: waiting requests take free slots by
  host bookkeeping and per-slot carry edits, no dispatch of their own
  (_admit_inline)] then [page preallocation for every step in flight
  and this block's] then [ONE fused block of decode_steps_per_tick
  steps (_mixed_block): every step, a decode-phase slot advances one
  token (or, under speculation, one draft/verify round) while up to P
  slots in prefill phase chew a C-token chunk of their prompt, packed
  into one forward — a single jitted scan, engine._packed_scan or
  _mixed_spec_scan[_win] — CHAINED on the previous block's
  device-resident carry]. With no prompt in flight the block carries
  no chunk and is a decode block (`bf_decode_block[_win]`). Up to
  RuntimeConfig.inflight_blocks blocks stay in flight
  (dispatch-ahead): block t+1 is dispatched before block t is
  drained, so the tick's host section — admission, operand assembly,
  emission — overlaps the device computing the newer blocks instead
  of idling it: a drain reads only arrays the blocks it drains
  produced themselves and launches no program to read them, so its
  fetch returns when the oldest block ends. A membership change the
  blocks in flight cannot absorb (preemption under page pressure,
  cancel, an expired deadline, a seq-parallel lane dispatch) forces a
  FULL drain barrier so host and device bookkeeping reconcile before
  the next dispatch. Admission forces none. A finish surfacing at a
  lazy drain is taken there, with the newer blocks still in flight
  (tick()'s docstring has the argument), except under speculation
  and with the seq-parallel lane, which keep the barrier. Speculative
  mode dispatches speculative mixed blocks through the same pipeline:
  drafts come from a device-resident token history, acceptance (with
  the rejection-sampling correction at temperature > 0) is computed
  inside the scan, and blocks chain on the (history, budgets) carry —
  no per-round barrier. A long prompt continues across steps and
  ticks a chunk at a time, so a max-length admission never holds the
  decoding requests for more than a step's chunks
  (prefill_inline_budget tokens).
* Admission allocates pages for prompt+1; each decode step grows a slot's
  pages just-in-time. If the pool is exhausted, the youngest running
  request is PREEMPTED (pages freed, request requeued; its prompt +
  generated-so-far become the new prompt and are recomputed on
  readmission — vLLM-style recompute preemption).
* Per-request sampling: temperature is a per-slot device array;
  stop-token/max-tokens checks are host-side (the host sees every token
  anyway when streaming).
"""
from __future__ import annotations

import contextlib
import itertools
import math
import statistics
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from butterfly_tpu.cache.allocator import make_page_allocator
from butterfly_tpu.cache.ssm_state import state_info
from butterfly_tpu.engine.serving import (
    LAUNCH_SPAN, ServingEngine, sample_batched)
from butterfly_tpu.obs.profile import run_delay_s
from butterfly_tpu.obs.registry import (
    BATCH_BUCKETS, LATENCY_BUCKETS, TOKEN_BUCKETS, MetricsRegistry)
from butterfly_tpu.obs.ticklog import TICK_PHASES, TickLog

#: spec_accept_rate histogram buckets: acceptance fractions in [0, 1]
#: (upper bounds; the 1.0 bucket is the all-drafts-accepted round)
SPEC_ACCEPT_BUCKETS = (0.01, 0.125, 0.25, 0.375, 0.5,
                       0.625, 0.75, 0.875, 1.0)


def _device_ready(x) -> bool:
    """Non-blocking completion probe for a device array (jax.Array
    .is_ready — true once the async dispatch has materialized it). On a
    runtime without the probe, report not-ready: the starvation clock
    then starts at full barriers only (which need no probe) instead of
    claiming a wait on every tick."""
    try:
        return bool(x.is_ready())
    except AttributeError:
        return False


#: a tick is a STALL (tick record `stall`, flight recorder note `stall`)
#: when its wall took more than STALL_FACTOR times the median of the
#: last 64 ticks that launched a block and compiled nothing, and more
#: than STALL_MIN_S seconds, whatever phase held it; so is a block fetch
#: by the same rule over the last 64 block fetches
STALL_FACTOR = 10.0
STALL_MIN_S = 0.25
#: what a SAMPLED tick's clock reads (the thread's CPU clock at every
#: span boundary, the process's at both ends) may cost a tick on
#: average. On a plain Linux host they are fast calls (0.3 and 0.7 us)
#: and every tick is sampled; on the sealed machine the benchmark's chip
#: sits in they are slow ones (5.8 us in a loop, the process's 20-40 us
#: inside a serving tick; both move in steps of 10 ms) and one tick in
#: `Scheduler._cpu_period` is (seven to nine there)
CPU_CLOCK_BUDGET_S = 25e-6
#: span boundaries of a steady tick (ten spans, entered and left, and
#: the starvation clock's start)
SPAN_READS_A_TICK = 24


def _thread_time_cost() -> float:
    """Seconds one read of the thread's CPU clock takes HERE: the least
    of three timings of eight reads."""
    cost = math.inf
    for _ in range(3):
        t0 = time.monotonic()
        for _ in range(8):
            time.thread_time()
        cost = min(cost, (time.monotonic() - t0) / 8)
    return cost


@dataclass
class Request:
    id: int
    prompt: List[int]
    max_new_tokens: int = 128
    temperature: float = 0.0
    stop_token: int = -1
    # client-supplied passthrough id (X-Request-Id / body "request_id"):
    # appears verbatim in traces so client logs join server timelines
    client_id: Optional[str] = None
    # priority class: "interactive" sheds last and is preempted last;
    # "batch" is the first shed under predicted-TTFT pressure and the
    # preferred preemption victim under page pressure
    priority: str = "interactive"
    # absolute time.monotonic() deadline (None = none declared). The
    # scheduler scrubs expired waiters every tick and cancels expired
    # runners at the next drain barrier — an expired request never
    # occupies a decode slot past its budget.
    deadline_s: Optional[float] = None
    # per-request speculation opt-out (only meaningful when the server
    # runs with speculative_gamma > 0): False rides the spec block but
    # ignores its drafts — the slot emits one exact plain-decode sample
    # per verify round (speculative_accept spec_mask semantics)
    speculative: bool = True
    # where the deadline fired ("waiting" | "running"), for the 504 body
    expired_where: Optional[str] = None
    # runtime state
    output: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    state: str = "waiting"  # waiting | prefilling | running | finished | cancelled
    prefilled: int = 0      # prompt tokens already in the KV cache
    preemptions: int = 0
    t_arrive: float = field(default_factory=time.monotonic)
    # last time the request entered the waiting queue (submit or
    # preemption): the queue_wait_seconds histogram measures from here
    t_enqueued: float = field(default_factory=time.monotonic)
    # when a server received the request (its handler's clock, before
    # the wait for the serving lock): submit() queues by it
    t_recv: Optional[float] = None
    # prefix-cache hit length at the LAST admission: prefill_tokens
    # histogram observes len(prompt) - this (only tokens actually run)
    cached_at_admit: int = 0
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None
    t_finish: Optional[float] = None
    on_token: Optional[Callable[["Request", int], None]] = None
    on_finish: Optional[Callable[["Request"], None]] = None

    @property
    def done(self) -> bool:
        return self.state in ("finished", "cancelled", "expired")

    @property
    def all_tokens(self) -> List[int]:
        """Prompt + generated-so-far: what a (re)prefill must cover."""
        return self.prompt + self.output

    @property
    def ttft(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_arrive


class Scheduler:
    """Continuous batching over a ServingEngine."""

    def __init__(self, engine: ServingEngine, seed: int = 0,
                 tracer=None, registry: Optional[MetricsRegistry] = None,
                 slo_ttft_s: Optional[float] = None,
                 slo_itl_s: Optional[float] = None,
                 flightrec=None, timeseries=None):
        self.engine = engine
        # Anomaly flight recorder (obs/ticklog.py FlightRecorder),
        # opt-in like the tracer: None keeps every call site a single
        # attribute-is-None check. When on, the scheduler notes
        # admission/preempt/shed/expiry/barrier/flush events into its
        # bounded ring and polls the trigger predicates once per tick.
        self.flightrec = flightrec
        # Periodic signal-history recorder (obs/timeseries.py
        # SignalRecorder), opt-in with the same None contract: when
        # off, the per-tick cost is one attribute-is-None check; when
        # on, _record_tick asks due() (one monotonic compare) and
        # samples the gauge/rate signal set at the recorder's interval.
        # It lives on the scheduler — not the server — so bench runs
        # record trajectories without an HTTP surface.
        self.timeseries = timeseries
        # Tracing is opt-in: trace=None keeps every hot-path call site a
        # single None check (obs/trace.py overhead contract). When on,
        # the engine shares the tracer for dispatch-level events.
        self.trace = tracer
        if tracer is not None and hasattr(engine, "tracer"):
            engine.tracer = tracer
        rt = engine.runtime
        max_pages = engine.cache.page_table.shape[1]
        if rt.prefix_caching:
            from butterfly_tpu.cache.prefix import PrefixCachingAllocator
            self.alloc = PrefixCachingAllocator(
                engine.cache.num_pages - 1, engine.cache.page_size, max_pages)
        else:
            self.alloc = make_page_allocator(engine.cache.num_pages - 1,
                                             engine.cache.page_size, max_pages,
                                             num_slots=engine.num_slots)
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []
        # Prefill chunks and decode/spec tokens ride ONE fused block per
        # tick (engine._packed_scan, and under speculation
        # _mixed_spec_scan[_win]): admission is a host-side carry edit
        # between dispatches (_seed_mixed_slot), never a drain barrier
        # or a dispatch of its own.
        # per-step chunk width C: under spec the verify shape pins it
        # to gamma+1; otherwise the inline budget (clamped by the tick
        # chunk budget) IS the width — one prefilling slot chews C
        # tokens per scan step, as one chunk of the packed step
        self._mixed_chunk = (rt.speculative_gamma + 1) if rt.speculative_gamma > 0 \
            else max(1, min(rt.prefill_inline_budget, rt.prefill_chunk))
        # concurrent-prefill cap — THE ITL-tail knob: at most this many
        # slots may be in prefill phase at once, so a scan step never
        # chews more than ~prefill_inline_budget prompt tokens while
        # decode slots wait on it. It is also P, the chunks a packed
        # step carries: the device gives the p-th slot in prefill phase
        # chunk p, so one more than P would wait unseen by the lockstep
        # simulation below
        self._mixed_max_pf = max(1, rt.prefill_inline_budget // self._mixed_chunk)
        # mixed-dispatch device carries: the per-slot chunk cursor
        # (DONATED to every mixed block, rebound from its result —
        # BTF002 contract) and, non-spec, the prompt-buffer rows the
        # prefill lanes read (under spec the token-history carry
        # doubles as the buffer). _plen_host is the per-slot prompt
        # length operand (host-owned; 0 marks a slot decode-phase).
        self._cursor_dev = None
        self._pbuf_dev = None
        self._plen_host = np.zeros((engine.num_slots,), np.int32)
        # The prefill GROUP: requests admitted to slots whose prompts are
        # not yet fully in the KV cache. Their lanes ride the fused
        # blocks in prefill phase (at most _mixed_max_pf at once get a
        # chunk a step); a member leaves at the drain of the block its
        # prompt completed in (_mixed_transitions).
        self._prefill_group: List[Request] = []
        # Long-prompt seq-parallel lane (ISSUE 20): prompts longer than
        # RuntimeConfig.seq_parallel_threshold prefill through chunked
        # seq-parallel dispatches (engine.sp_prefill_chunk — ring
        # attention over the mesh's seq axis, K/V landing in the
        # ordinary page pool) and then decode as normal paged slots. At
        # most ONE request occupies the lane: each chunk dispatch
        # already spans every seq-axis device, so a second concurrent
        # long prefill would only queue behind the first's dispatches.
        self._sp_group: List[Request] = []
        self._sp_enabled = (rt.seq_parallel_threshold > 0
                            and engine.supports_seq_parallel)
        if rt.seq_parallel_threshold > 0 and not self._sp_enabled:
            import warnings
            warnings.warn(
                "seq_parallel_threshold set but the engine cannot "
                "seq-parallel (needs a mesh with seq > 1 and stage == "
                "1); long prompts take the single-device chunk path",
                RuntimeWarning, stacklevel=2)
        # tokens per seq-parallel dispatch: each shard chews about a
        # prefill_chunk worth of work, so one lane dispatch costs a
        # tick roughly what a dense prefill round does
        N = engine.sp_degree
        spc = rt.seq_parallel_chunk or N * max(1, rt.prefill_chunk)
        self._sp_chunk = -(-spc // max(1, N)) * max(1, N)
        self.slots: List[Optional[Request]] = [None] * engine.num_slots
        self._ids = itertools.count()
        self._key = jax.random.PRNGKey(seed)
        self._next_tokens = np.zeros((engine.num_slots,), np.int32)
        # In-flight fused blocks, tagged tuples in dispatch order:
        #   ("mixed",  final [S], (block [k, S], valid [k, S]), k,
        #              snapshot, t, pf_done slots, emit_vec [S])
        #   ("mixed_spec", hist_len [S], (toks, valid) [R, S, C], R,
        #              snapshot, t, pf_done slots, None)
        # where snapshot maps slot -> (request, generation); the
        # entries carry the slots whose prefill completed
        # inside the block (drain-time state transitions) and, plain
        # mixed, the host-simulated per-slot emission counts the next
        # dispatch's budget look-ahead subtracts. Each tick
        # dispatches ONE jitted scan chained on the previous block's
        # device-resident
        # carry, and up to RuntimeConfig.inflight_blocks of them stay
        # undrained
        # (dispatch-ahead): the host fetches only the OLDEST block when
        # the queue fills, so its drain + the next tick's scheduling
        # run while the device computes the newer blocks. This is what
        # closes the serving loop toward the isolated-decode ceiling
        # and what makes it survive a slow host<->device round trip
        # (the fetch of block t overlaps the device running t+1).
        self._inflight: List[tuple] = []
        # Batch-membership epoch: bumped whenever the running set, the
        # pending-first set, or any runner's drained output changes
        # (admission completing, finish, preemption, any drain).
        # _assemble caches the host operand assembly — the
        # active/temps/stops/base-budget arrays and the slot snapshot —
        # keyed on it, so back-to-back blocks over an unchanged batch
        # skip the per-slot Python rebuild and the np.asarray churn.
        self._epoch = 0
        self._operands_epoch = -1
        self._operands: Optional[tuple] = None
        # The starvation clock: the device's wait for the host, on the
        # host's clock. `_starved_by` is None while the device has work
        # (or the server has none to give it); while the clock runs it
        # is {innermost span name: seconds}, fed by _lap at every span
        # boundary, and `_starved_cause` says why the clock started.
        # _starve starts it, the launch span's end (_fed) stops it.
        self._starved_by: Optional[Dict[str, float]] = None
        self._starved_cause: Optional[str] = None
        # the waits that this tick's launches ended: seconds (None
        # until the tick launches), the first one's cause, by span
        self._tick_starved: Optional[float] = None
        self._tick_starved_cause: Optional[str] = None
        self._tick_starved_by: Dict[str, float] = {}
        # this tick's start less the last tick's end
        self._tick_gap = 0.0
        # a /debug/profile capture is running (ServerState._maybe_profile
        # sets it): the tick record says so, because a capture
        # multiplies the host's phases
        self.profiled = False
        # the last 64 block fetches, seconds, and the last 64 ticks that
        # launched a block and compiled nothing, (wall, CPU seconds,
        # wall by phase, wall by span): what a stall is told by
        self._fetches: Deque[float] = deque(maxlen=64)
        self._sound_ticks: Deque[tuple] = deque(maxlen=64)
        # this tick's stall, once one is noted: one note a tick
        self._tick_stall: Optional[Dict] = None
        # First tokens sampled on-device as the seq-parallel lane's
        # prompt completed (_finish_prefill), not yet fetched:
        # [(req, generation=req.preemptions, slot, device scalar)].
        # Fetched with the next drain, all in one jax.device_get (a
        # per-admission host fetch would pay the full dispatch+fetch
        # RTT per request).
        self._pending_first: List[tuple] = []
        # Membership index over _pending_first, keyed (request id,
        # preemptions) and refreshed at drain time: _assemble's
        # budget computation and _written ask "does req have an
        # undrained first token?" per runner — a set lookup instead of
        # the old O(running x pending) linear scan.
        self._pending_first_keys: set = set()
        # Device twin of _next_tokens: the decode chain's input vector.
        # Admissions write their first token into it with a device-side
        # .at[].set, so dispatching never needs the host values.
        self._next_dev = None
        # Speculative-mode device carries (allocated only with
        # speculative_gamma > 0): the per-slot token history
        # [S, cache.max_seq] + live lengths the on-device drafter reads
        # (admissions write their prompt + first token in; spec blocks
        # append their own emissions in-scan), and the remaining-budget
        # vector the chained dispatches thread through
        # (None = rebuild from host state at the next dispatch — set at
        # every full drain barrier, when the host again knows every
        # emitted token).
        self._spec_mode = rt.speculative_gamma > 0
        self._hist_dev = None
        self._hist_len_dev = None
        self._spec_rem = None
        if self._spec_mode:
            H = engine.cache.max_seq
            self._hist_dev = jnp.zeros((engine.num_slots, H), jnp.int32)
            self._hist_len_dev = jnp.zeros((engine.num_slots,), jnp.int32)
        # A finish that surfaces at a lazy drain is taken there, with
        # the newer blocks in flight, where the scheduler runs without
        # speculation and without the seq-parallel lane (tick()'s
        # docstring). SEPARATE by mode, not a parameter: the
        # speculative path resets the device's budget carry (_spec_rem)
        # to host truth at a finish barrier, and the seq-parallel lane
        # donates the pool binding in dispatches of its own; they keep
        # the barrier.
        self._finish_inline = not self._spec_mode and not self._sp_enabled
        # Typed instruments (obs/registry.py) replace the old ad-hoc
        # Dict[str, float]: counters for the monotonic totals, fixed-
        # bucket histograms for the latency/size distributions /metrics
        # exposes as real _bucket/_sum/_count series. metrics() still
        # returns the legacy flat dict, assembled from the registry.
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        reg = self.registry
        self._c_requests = reg.counter(
            "requests_total", "Requests submitted")
        self._c_finished = reg.counter(
            "requests_finished", "Requests completed")
        self._c_tokens = reg.counter(
            "tokens_generated_total",
            "Tokens generated across all requests")
        self._c_preempt = reg.counter(
            "preemptions_total",
            "Recompute preemptions under page pressure")
        self._c_spec_fwd = reg.counter(
            "spec_forwards_total",
            "Speculative verify forwards that did work (spec-block "
            "rounds with at least one live slot)")
        self._c_spec_acc = reg.counter(
            "spec_drafts_accepted_total",
            "Draft tokens accepted by speculative verify")
        self._c_spec_tok = reg.counter(
            "spec_block_tokens_total",
            "Tokens emitted from speculative verify blocks (accepted "
            "drafts + corrections/bonus samples); divided by "
            "spec_forwards_total this is tokens/forward — the number "
            "speculation exists to push past 1")
        self._h_accept = reg.histogram(
            "spec_accept_rate",
            "Per-slot-round draft acceptance fraction (accepted / "
            "gamma) over emitted rounds of speculating requests — 0 "
            "means every round paid a full verify for one token",
            SPEC_ACCEPT_BUCKETS)
        # Barrier-cause accounting (ISSUE 15): the single counter grew
        # a {cause} label so the bench can say WHICH membership-change
        # class costs the pipeline. The unlabeled sum survives as the
        # metrics()["drain_barriers_total"] compat key (and as the sum
        # of the labeled children in the exposition).
        self._c_barriers = reg.counter_family(
            "drain_barriers_total",
            "FULL drain barriers (every in-flight block fetched, "
            "pipeline restarts cold), by membership-change cause "
            "(finish, page_pressure, cancel, spec, idle, expired, "
            "sp_prefill, flush). Compare the sum with spec_forwards_total "
            "/ tick count: a healthy pipeline drains lazily and "
            "barriers only on membership changes, never once per "
            "decode or spec round", ("cause",))
        self._c_finish_inline = reg.counter(
            "finishes_inline_total",
            "Requests whose finish surfaced at a lazy drain and was "
            "taken there, with the newer blocks still in flight and no "
            "full barrier (no speculation, no seq-parallel lane); a "
            "finish with either counts in "
            "drain_barriers_total{cause=\"finish\"}")
        self._c_overlap = reg.counter_family(
            "drain_overlap_total",
            "Lazy drains by what the device was doing when the fetch "
            "of the oldest block returned: overlapped (the newest "
            "block in flight was still running, so the tick's host "
            "work hides behind it) or exposed (it had ended: the "
            "device idles until the next launch)", ("state",))
        self._h_ttft = reg.histogram(
            "ttft_seconds",
            "Time to first token (submit -> first token drained)",
            LATENCY_BUCKETS)
        self._h_itl_mean = reg.histogram(
            "itl_req_mean_seconds",
            "Per-finished-request MEAN inter-token gap — the effective "
            "streaming rate a client experiences", LATENCY_BUCKETS)
        self._h_queue_wait = reg.histogram(
            "queue_wait_seconds",
            "Wait from submit (or preemption) to slot admission",
            LATENCY_BUCKETS)
        self._h_batch = reg.histogram(
            "batch_size", "Decoding slots active per scheduler tick",
            BATCH_BUCKETS)
        self._h_prefill_tokens = reg.histogram(
            "prefill_tokens",
            "Prompt tokens prefilled per admission (prefix-cache hits "
            "excluded)", TOKEN_BUCKETS)
        self._c_sp_tokens = reg.counter(
            "seq_parallel_prefill_tokens_total",
            "Prompt tokens prefilled through the long-prompt "
            "seq-parallel lane (chunked ring-attention dispatches; "
            "zero when seq_parallel_threshold is off or no prompt "
            "exceeded it)")
        self._h_decode_block = reg.histogram(
            "decode_block_seconds",
            "Fused decode block in-flight residency: dispatch to "
            "stacked drain (covers decode_steps_per_tick device steps "
            "plus, under dispatch-ahead, the ticks the block waited "
            "undrained while newer blocks ran)", LATENCY_BUCKETS)
        self._h_bubble = reg.histogram(
            "device_bubble_seconds",
            "The starvation clock at each program launch: 0 when the "
            "launch found the device busy; otherwise the seconds from "
            "the moment the host learned that nothing it had launched "
            "still ran (a barrier's or an exposed lazy drain's fetch "
            "returned, or a tick began with the newest block done) to "
            "the launch's return. A lower bound: idle time before the "
            "host looked is not counted",
            LATENCY_BUCKETS)
        self._g_inflight = reg.gauge(
            "inflight_depth",
            "Decode blocks in flight (dispatched, not yet drained) at "
            "the end of the last scheduler tick")
        # Write-combined KV window (RuntimeConfig.kv_write_combine):
        # every drain flushes the staged window into the page pool with
        # one scatter per pool tensor, BEFORE any finish registers or
        # reclaims pages. The histogram times the host-side flush
        # dispatch section (on an async backend the device cost shows
        # up in decode_block_seconds instead); the counter takes each
        # flush's count once it is ready (_count_flushed), never in
        # the drain that dispatched it.
        self._h_kv_flush = reg.histogram(
            "kv_flush_seconds",
            "Host wall time of the write-combined KV window flush "
            "dispatch at a drain (kv_write_combine; one pool scatter "
            "per drain instead of one per token per layer)",
            LATENCY_BUCKETS)
        self._c_kv_flushed = reg.counter(
            "kv_window_tokens_flushed_total",
            "Staged K/V tokens flushed from the write-combined decode "
            "window into the page pool (kv_write_combine); tokens "
            "whose requests died before a flush are dropped, not "
            "counted")
        # a mixed block's chunks: positions offered (steps x chunks x
        # chunk width of a block dispatched with a prompt in flight)
        # and prompt tokens they consumed; their ratio is the fill
        self._c_chunk_offered = reg.counter(
            "mixed_chunk_positions_total",
            "Prefill chunk positions that mixed blocks carried (steps "
            "x chunks x chunk width of every block dispatched with a "
            "prompt in flight), real or filler")
        self._c_chunk_tokens = reg.counter(
            "mixed_chunk_tokens_total",
            "Prompt tokens consumed inside mixed blocks; over "
            "mixed_chunk_positions_total it is the chunk's fill")
        self._kv_flushes: Deque[float] = deque(maxlen=4096)
        # Flush counts dispatched and not yet read, in device order: a
        # drain never waits for the flush it has just dispatched (it
        # queues behind every block in flight). A lazy drain adds the
        # counts that are ready; a full barrier reads what is left.
        self._flush_counts: List = []
        # Host-RAM KV tier (ISSUE 17, cache/hosttier.py): prefix-cache
        # eviction demotes page bytes to host DRAM (optionally spilling
        # to disk) instead of dropping them, and admission's prefix
        # walk revives them on a hit — the evict/revive hooks installed
        # on the allocator here are the only device-touching halves
        # (read_pages on evict, write_pages on revive); the tier itself
        # is pure host state. Off (None) unless prefix caching is on
        # AND a tier budget is declared.
        self.host_tier = None
        self._g_tier_hit = None
        self._tier_restores: Deque[float] = deque(maxlen=4096)
        if rt.prefix_caching and (rt.host_kv_tier_mb or 0) > 0:
            from butterfly_tpu.cache.hosttier import HostKVTier
            self.host_tier = HostKVTier(
                int(rt.host_kv_tier_mb * 1024 * 1024),
                spill_dir=rt.host_kv_tier_dir)
            self.alloc.on_evict = self._tier_save
            self.alloc.reviver = self._tier_revive
            self._c_tier_saved = reg.counter(
                "kv_tier_pages_saved_total",
                "KV pages demoted to the host tier at prefix-cache "
                "eviction (read_pages -> host DRAM) instead of dropped")
            self._c_tier_restored = reg.counter(
                "kv_tier_pages_restored_total",
                "KV pages revived from the host tier on a prefix hit "
                "(import_page + write_pages) — prefill work the tier "
                "saved")
            self._c_tier_miss = reg.counter(
                "kv_tier_misses_total",
                "Prefix-walk registry misses the host tier could not "
                "serve either (the chain was never demoted, or aged "
                "out of the tier's budget)")
            self._h_tier_restore = reg.histogram(
                "kv_tier_restore_seconds",
                "Host wall time to revive one page from the host tier "
                "(tier lookup + import_page + the device scatter)",
                LATENCY_BUCKETS)
            self._g_tier_hit = reg.gauge(
                "kv_tier_hit_rate",
                "Fraction of host-tier lookups served (restores / "
                "(restores + misses), all paths including export) — "
                "the tier-effectiveness signal dashboards sparkline")
        # SLO attainment (ISSUE 7): declared objectives make latency a
        # pass/fail measurement per request instead of a percentile to
        # eyeball. None = no objective declared: zero accounting runs
        # (the counters exist but never increment).
        self.slo_ttft_s = slo_ttft_s
        self.slo_itl_s = slo_itl_s
        self._c_slo_ttft_ok = reg.counter(
            "slo_ttft_ok_total",
            "First tokens delivered within the declared TTFT objective "
            "(--slo-ttft-ms)")
        self._c_slo_itl_ok = reg.counter(
            "slo_itl_ok_total",
            "Finished requests whose mean inter-token gap met the "
            "declared ITL objective (--slo-itl-ms)")
        self._c_slo_viol = reg.counter_family(
            "slo_violations_total",
            "Requests that missed a declared latency objective, by "
            "objective kind", ("kind",))
        self._g_slo_burn = reg.gauge(
            "slo_burn_rate",
            "Fraction of the last 256 finished requests that violated "
            "ANY declared objective (0 = meeting SLO, 1 = burning the "
            "whole error budget) — the rolling signal SLO-aware "
            "admission and autoscaling read")
        # Overload protection (ISSUE 8): deadline expiry + SLO-aware
        # admission shedding. Shedding activates only with a declared
        # TTFT objective AND observed latency evidence — a cold server
        # never sheds blind.
        self._c_deadline = reg.counter_family(
            "deadline_expired_total",
            "Requests that blew their declared deadline (deadline_ms / "
            "X-Deadline-Ms), by where they died: scrubbed from the "
            "waiting queue, or cancelled out of a decode slot",
            ("where",))
        self._c_shed = reg.counter_family(
            "shed_total",
            "Requests shed at admission (429) because predicted TTFT "
            "busts the declared --slo-ttft-ms, by priority class "
            "(batch sheds at the objective, interactive at "
            "interactive_slack x it)", ("priority",))
        # interactive requests tolerate this multiple of the TTFT
        # objective before shedding — batch is always shed first
        self.interactive_slack = 2.0
        # rolling attainment window backing the burn-rate gauge
        self._slo_window: Deque[float] = deque(maxlen=256)
        # latency reservoirs: both bounded to the same recent window so
        # the two adjacent metrics share time-horizon semantics (and a
        # long-lived server doesn't leak one float per request forever)
        self._ttfts: Deque[float] = deque(maxlen=4096)
        # inter-token gaps (seconds), bounded reservoir of the most
        # recent gaps across all requests — the latency a decoding
        # request experiences when admissions interleave (the quantity
        # chunked prefill exists to bound). With pipelined dispatch,
        # tokens surface in per-tick bursts, so raw gap percentiles
        # bimodalize (p50 ~ 0, p95 ~ tick); _itl_means tracks each
        # finished request's MEAN gap (t_last - t_first)/(n - 1) — the
        # effective per-token rate a streaming client experiences.
        self._itls: Deque[float] = deque(maxlen=4096)
        self._itl_means: Deque[float] = deque(maxlen=4096)
        # -- tick anatomy (ISSUE 15) -----------------------------------------
        # Per-tick phase attribution: tick() zeroes the accumulator,
        # the structural sections run inside _span(), which adds their
        # exclusive time.monotonic() deltas (host->host arithmetic
        # only — the timers themselves must never sync, BTF003 covers
        # these paths), and the record lands in the bounded timeline
        # ring GET /debug/ticks serves.
        self.ticklog = TickLog(capacity=512)
        self._tick_phases: Dict[str, float] = {p: 0.0 for p in TICK_PHASES}
        # the span stack: the phase each open span is charged to,
        # innermost last, over "other" (the tick itself); _span_t is
        # the last boundary, from which the innermost span is owed
        self._span_stack: List[str] = ["other"]
        self._span_t = time.monotonic()
        # the tick thread's CPU clock at that boundary: a lap's wall
        # less its CPU seconds is what the thread WAITED in the span
        # (for the device, a lock, the interpreter lock, a CPU). Every
        # tick reads it at its start and end; one tick in _cpu_period
        # (`_cpu_on`) at every span boundary
        self._span_cpu = self._tick_cpu0 = time.thread_time()
        self._cpu_on = False
        # what a read of each CPU clock costs: the thread's timed once,
        # here; the process's at every read (it sums the threads there
        # are), and with it how many ticks share a sampled one
        self._thread_cost = _thread_time_cost()
        self._proc_cost = 0.0
        self._cpu_period = 1
        self._tick_proc0 = 0.0
        # the tick under way: its wall by the innermost span's own name
        # and, where the CPU clock is on, its off-CPU seconds likewise
        self._tick_wall_by: Dict[str, float] = {}
        self._tick_off_cpu: Dict[str, float] = {}
        self._tick_t0 = self._span_t
        # the innermost open span by its own name (sub-spans too):
        # "other" inside a tick, "outside_tick" between two
        self._span_name = "outside_tick"
        # the engine's put and launch spans run through _span, so their
        # time lands in the tick record and the launch's end is known
        engine.span = self._span
        self._tick_causes: List[str] = []
        # requests that finished at this tick's lazy drain with no barrier
        self._tick_finishes_inline = 0
        # the tick's lazy drain, if it had one with a newer block in
        # flight: was that block still running when the fetch returned
        self._tick_overlapped: Optional[bool] = None
        # ServerState._loop's wait for the serving lock before the
        # tick under way (it sets this; the tick record carries it)
        self.loop_lock_s = 0.0
        # compilations of this process (obs/profile.py count_compiles
        # feeds them): a tick that compiles stalls every stream
        self._c_compiles = reg.counter(
            "compiles_total",
            "Programs built by the backend compiler or fetched from "
            "the persistent compile cache (one per new program shape)")
        reg.counter(
            "compile_seconds_total",
            "Seconds spent tracing, lowering and compiling programs")
        # the interpreter's collections (obs/profile.py
        # count_collections feeds them, whichever thread collects): one
        # holds the interpreter lock, so the tick waits through it
        self._c_gc_s = reg.counter(
            "gc_seconds_total",
            "Seconds the interpreter spent in garbage collections, with "
            "its lock held: every thread of the process waits")
        gens = reg.counter_family(
            "gc_collections_total",
            "Garbage collections of the interpreter, by the oldest "
            "generation each examined (2: a full collection)",
            ("generation",))
        self._c_gc_gen = [gens.labels(str(g)) for g in range(3)]
        # the process's figures as the last tick ended (_account):
        # collections' seconds and counts by generation, programs
        # compiled; and the tick thread's own run delay as this tick
        # began (its thread's to read)
        self._tick_base: tuple = self._process_figures()
        self._tick_delay0: Optional[float] = None
        # the fetch's device wait within this tick's drains: feeds
        # the host/device split (tick_host_frac / tick_device_frac) —
        # the fetch is the one tick section that blocks on the device
        self._tick_fetch = 0.0
        self._t_host_total = 0.0
        self._t_device_total = 0.0
        # what the routing of the mixed blocks drained this tick asked
        # of the experts (engine _packed_scan's `load`, fetched with
        # the blocks' tokens in the drain's one device_get): one
        # [touched, rows_max, rows_mean] a block. Empty for a dense
        # model. The gauges hold the newest block's.
        self._tick_expert_loads: List = []
        # a model with recurrent layers (Mamba-2, Gated DeltaNet or
        # Mamba-1): the same vector ends in the block's recurrence rows
        # and state resets (sums over its steps); the tick record holds
        # their sums over the blocks it drained, with the steps those
        # ran. None for every other model
        self._has_ssm = engine.cfg.has_ssm
        self._tick_ssm: Optional[List[float]] = None
        # a latent-attention model: the vector ends in the cached rows
        # the block's decode rows read, over its layers and steps; the
        # tick record holds [rows, steps] over the blocks it drained
        self._is_latent = engine.cfg.is_latent
        # with an indexer over its rows the vector holds the indexer's
        # three means there instead (kv_rows_*), as every such model's
        self._has_indexer = engine.cfg.has_indexer
        self._tick_latent: Optional[List[float]] = None
        # one chip's share of a deployment's experts (experts_held): the
        # vector ends in the block's expert assignments that fell on a
        # held expert and all of them (the mean over the layers, sums
        # over its steps); the tick record holds their sums over the
        # blocks it drained
        self._experts_held = engine.cfg.experts_held > 0
        self._tick_share: Optional[List[float]] = None
        # a cache by kind (cache/paged.py ring_pages: the sliding
        # layers' rows in a ring a slot, the full layers' under the page
        # table): the vector holds, before a share's two, the rows the
        # sliding layers' decode rows read and what they would have read
        # with no window (sums over the block's steps); the tick record
        # holds their sums over the blocks it drained beside the pages
        # held BY KIND and the rings' wraps. The free list and
        # `pages_free` are the full kind's, as ever: a slot's ring is
        # its own from admission to release, so admission, growth and
        # preemption have nothing to learn of it but that it is there
        self._ring_pages = engine.cache.ring_table.shape[1] \
            if engine.cache.by_kind else 0
        self._tick_swa: Optional[List[float]] = None
        self._ring_wraps: Dict[int, int] = {}
        self._c_swa_read = reg.counter(
            "swa_rows_read_total",
            "Cached rows the sliding layers' decode rows read (a live row "
            "at position p reads min(p + 1, window) in each such layer); "
            "stays 0 for a cache of one kind")
        self._c_swa_whole = reg.counter(
            "swa_rows_whole_total",
            "Cached rows the sliding layers' decode rows would have read "
            "with no window (p + 1 a layer); stays 0 for a cache of one "
            "kind")
        self._c_ring_wraps = reg.counter(
            "kv_ring_wraps_total",
            "Times a stream's written length passed a whole ring of the "
            "sliding layers' pages (every entry of it rewritten once "
            "more); stays 0 for a cache of one kind")
        self._g_pages_slide = reg.gauge(
            "kv_pages_slide",
            "Pages of the sliding layers' pool that slots with a request "
            "hold: the ring's pages x those slots, whatever their "
            "contexts; 0 for a cache of one kind")
        self._g_pages_full = reg.gauge(
            "kv_pages_full",
            "Pages of the full layers' pool in use (the page table's): "
            "every layer's for a cache of one kind")
        self._c_expert_rows_local = reg.counter(
            "expert_rows_local_total",
            "Expert assignments (a step's real rows x experts a token, "
            "the mean over the layers that route) that fell on an expert "
            "this chip HOLDS (ModelConfig.experts_held: its share of a "
            "deployment's experts); stays 0 where every expert is held")
        self._g_latent_rows = reg.gauge(
            "latent_rows_read",
            "Cached latent rows that the decode rows of one step read, "
            "summed over the layers (cache/paged.py latent_paged_attend), "
            "the mean over the newest mixed block's steps; 0 for a model "
            "without latent attention")
        # a model of n residual streams (hc_mult): the vector ends in the
        # positions whose streams the block's steps mixed; the tick
        # record holds [rows, steps] over the blocks it drained
        self._has_streams = engine.cfg.hc_mult > 0
        self._tick_hc: Optional[List[float]] = None
        self._c_hc_rows = reg.counter(
            "hc_rows_mixed_total",
            "Positions whose residual streams a mixed block's steps mixed "
            "(models/common.py stream_read / stream_write: a live decode "
            "row, a chunk's real columns); stays 0 for a model of one "
            "stream")
        self._g_ssm_state_bytes = reg.gauge(
            "ssm_state_bytes",
            "Bytes of recurrent state the slots hold for a model with "
            "Mamba-2, Gated DeltaNet or Mamba-1 layers "
            "(cache/ssm_state.py): fixed, "
            "whatever the streams' lengths; 0 for a model without")
        self._g_ssm_state_bytes.set(float(
            (state_info(engine.cfg, engine.num_slots) or {}).get("bytes", 0)))
        self._g_experts_touched = reg.gauge(
            "moe_experts_touched",
            "Distinct experts that the rows of one step touched in one "
            "layer, the mean over the newest drained block's steps and "
            "layers: what a dispatch that skips unrouted experts would "
            "still stream")
        self._g_expert_rows_max = reg.gauge(
            "moe_expert_rows_max",
            "Rows routed to the fullest expert of a layer in one step, "
            "the mean over the newest drained block's steps and layers")
        self._g_kv_rows_live = reg.gauge(
            "kv_rows_live",
            "Cached positions a live decode row could attend, the mean "
            "over the newest drained block's layers, rows and steps (a "
            "model with a sparse-attention indexer; 0 without)")
        self._g_kv_rows_selected = reg.gauge(
            "kv_rows_selected",
            "Cached positions a live decode row attended, the index_topk "
            "its indexer selected at most: the mean over the newest "
            "drained block's layers, rows and steps")
        self._g_kv_rows_moved = reg.gauge(
            "kv_rows_moved",
            "Rows of keys (and as many of values) that a live decode "
            "row's read moved out of the cache: its live rows where the "
            "kernel walks its pages and masks the selection, the selected "
            "rows where they are gathered; the same mean")
        # per-phase histograms in the registry: real _bucket series per
        # structural phase, so dashboards see distributions, not means
        self._h_phase = {
            p: reg.histogram(
                f"tick_phase_{p}_seconds",
                f"Host wall time of the '{p}' tick phase per tick "
                "(docs/serving.md tick-pipeline vocabulary)",
                LATENCY_BUCKETS)
            for p in TICK_PHASES}

    def _clocks(self) -> tuple:
        """A span boundary's two readings: the wall clock, and the tick
        thread's CPU clock in a tick that reads it there (None in the
        others)."""
        return (time.monotonic(),
                time.thread_time() if self._cpu_on else None)

    def _lap(self, now: float, cpu: Optional[float]) -> None:
        """A span boundary on both clocks (_clocks): the wall time since
        the last one is owed to the innermost open span's phase and to
        its own name, and what it holds beyond the tick thread's CPU
        time since then (the thread WAITED) to the span's own name in
        `off_cpu_by`. That difference is kept signed, so the table sums
        to the tick's wall less its CPU seconds whatever the CPU
        clock's step (10 ms where the kernel counts CPU time by timer
        ticks: a lap shorter than a step is charged none or a whole
        one, and only sums over many laps say what it used). While the
        starvation clock runs the wall also goes to the clock's table.
        Plain dict arithmetic — never a sync."""
        d = now - self._span_t
        self._span_t = now
        name = self._span_name
        self._tick_phases[self._span_stack[-1]] += d
        by = self._tick_wall_by
        by[name] = by.get(name, 0.0) + d
        if cpu is not None:
            by = self._tick_off_cpu
            by[name] = by.get(name, 0.0) + d - (cpu - self._span_cpu)
            self._span_cpu = cpu
        by = self._starved_by
        if by is not None:
            by[name] = by.get(name, 0.0) + d

    @contextlib.contextmanager
    def _span(self, name: str, **attrs):
        """One section of the tick on both clocks: a TraceAnnotation
        `bf.tick.<name>` in the profiler's trace (with no capture
        running: an atomic load, and `attrs` are never formatted),
        and exclusive time.monotonic() and time.thread_time() time in
        the tick record.
        Entering pauses the enclosing span's timer and leaving resumes
        it, so phases never overlap and sum to the tick's wall time. A
        TICK_PHASES name is charged to itself, any other name (a
        sub-span such as `drain.fetch`) to the phase around it; while
        the starvation clock runs the same time also goes to the
        clock's table under the span's OWN name (_lap). The end of the
        engine's launch span stops the clock (_fed). Yields the
        annotation (set_metadata adds what is known only at the
        end). Plain dict arithmetic — never a sync."""
        stack = self._span_stack
        self._lap(*self._clocks())
        stack.append(name if name in self._tick_phases else stack[-1])
        outer, self._span_name = self._span_name, name
        with TraceAnnotation("bf.tick." + name, **attrs) as ann:
            try:
                yield ann
            finally:
                self._lap(*self._clocks())
                stack.pop()
                self._span_name = outer
                if name == LAUNCH_SPAN:
                    self._fed(ann)

    def _starve(self, cause: str) -> None:
        """Start the starvation clock: the host has just learned that
        nothing it launched is still running, so from here to the next
        launch's return the device waits for the host. Three callers:
        a full barrier's fetch returned (`cause` is the barrier's), a
        lazy drain's fetch returned with the newest block in flight
        already done ("exposed"), a tick began with the newest block
        done ("late_tick"). The flush that a drain dispatches before
        its fetch runs right behind the fetched blocks and is under a
        millisecond (PERF.md section 5): it is not waited for, and
        counts as the device waiting. Where the device ran dry BEFORE
        the host looked (a probe that found the block done, a fetch
        that did not block), the time before the look is not counted:
        the clock is a lower bound, exact where the fetch blocked. A
        clock that already runs keeps its start and its cause."""
        if self._starved_by is None:
            self._lap(*self._clocks())
            self._starved_by = {}
            self._starved_cause = cause

    def _fed(self, ann) -> None:
        """A program launch has returned (the end of the engine's
        launch span): the device has work again. Stop the starvation
        clock if it runs and charge the wait to the tick under way,
        whichever tick it began in; a launch onto a busy device counts
        0.0. Feeds device_bubble_seconds, and the launch span carries
        `starved_ms`, so a trace shows the wait at the launch that
        ended it."""
        by, s = self._starved_by, 0.0
        if by is not None:
            self._starved_by = None
            s = sum(by.values())
            if self._tick_starved_cause is None:
                self._tick_starved_cause = self._starved_cause
            mine = self._tick_starved_by
            for k, v in by.items():
                mine[k] = mine.get(k, 0.0) + v
        self._tick_starved = (self._tick_starved or 0.0) + s
        self._h_bubble.observe(s)
        ann.set_metadata(starved_ms=1e3 * s)

    # -- public API ---------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 128,
               temperature: float = 0.0, stop_token: int = -1,
               on_token=None, on_finish=None,
               request_id: Optional[str] = None,
               priority: str = "interactive",
               deadline_s: Optional[float] = None,
               speculative: bool = True,
               lock_wait_s: Optional[float] = None,
               t_recv: Optional[float] = None) -> Request:
        # Reject what can never fit: a request that exceeds the per-seq
        # page limit or the whole pool would self-preempt forever.
        worst = -(-(len(prompt) + max_new_tokens) // self.alloc.page_size)
        if worst > self.alloc.max_pages_per_seq or worst > self.alloc.num_pages:
            raise ValueError(
                f"request needs {worst} KV pages (prompt {len(prompt)} + "
                f"max_new {max_new_tokens}) but the limit is "
                f"{min(self.alloc.max_pages_per_seq, self.alloc.num_pages)}")
        if priority not in ("interactive", "batch"):
            raise ValueError(f"unknown priority {priority!r}: expected "
                             "'interactive' or 'batch'")
        req = Request(id=next(self._ids), prompt=list(prompt),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      stop_token=stop_token, client_id=request_id,
                      priority=priority, deadline_s=deadline_s,
                      speculative=bool(speculative),
                      on_token=on_token, on_finish=on_finish,
                      t_recv=t_recv)
        # first come, first served by when the request was RECEIVED,
        # where the caller says: a server's handlers get here in the
        # order they won the serving lock, which under a burst is not
        # the order the requests came in
        at = len(self.waiting)
        while t_recv is not None and at and \
                (self.waiting[at - 1].t_recv or 0.0) > t_recv:
            at -= 1
        self.waiting.insert(at, req)
        self._c_requests.inc()
        if self.trace is not None:
            # a server passes what came before the scheduler: its wait
            # for the serving lock and when the request was received
            front = {} if lock_wait_s is None else \
                {"lock_wait_s": lock_wait_s, "t_recv": t_recv}
            self.trace.begin_request(req.id, request_id=request_id,
                                     prompt_len=len(prompt),
                                     max_new_tokens=max_new_tokens,
                                     **front)
        return req

    # -- overload protection (ISSUE 8) --------------------------------------

    def predict_ttft(self, prompt_len: int) -> Optional[float]:
        """Admission-time TTFT prediction for a hypothetical new
        arrival: the prefill backlog ahead of it (waiting prompts +
        unfinished prefill-group work + its own prompt) in
        prefill_chunk-budget rounds, plus one round per waiter ahead
        (slot contention), each round costed at the rolling
        per-request mean ITL — every chunk round shares a tick with a
        decode block, so the recent inter-token gap IS the tick cost a
        queued request pays. Returns None without latency evidence
        (cold server: never predict, never shed blind). Deliberately
        cheap — a misprediction costs one early 429 or one late
        admission, never correctness."""
        window = self._itl_means or self._itls
        if not window:
            return None
        tick_s = sum(window) / len(window)
        chunk = max(1, self.engine.runtime.prefill_chunk)
        backlog = prompt_len
        backlog += sum(len(r.all_tokens) - r.prefilled
                       for r in self._prefill_group)
        # seq-parallel lane work is shared N ways across the mesh
        backlog += sum(len(r.all_tokens) - r.prefilled
                       for r in self._sp_group) \
            // max(1, self.engine.sp_degree)
        backlog += sum(len(r.all_tokens) for r in self.waiting)
        rounds = -(-backlog // chunk) + len(self.waiting)
        return rounds * tick_s

    def shed_decision(self, prompt_len: int,
                      priority: str = "interactive") -> Optional[float]:
        """SLO-aware admission: seconds to advertise as Retry-After
        when the request should be SHED (predicted TTFT busts the
        declared objective), or None to admit. Batch sheds at the
        objective; interactive tolerates interactive_slack x it, so
        under rising load batch traffic is always turned away first.
        No declared --slo-ttft-ms = no shedding, ever."""
        if self.slo_ttft_s is None:
            return None
        pred = self.predict_ttft(prompt_len)
        if pred is None:
            return None
        limit = self.slo_ttft_s * (self.interactive_slack
                                   if priority == "interactive" else 1.0)
        if pred <= limit:
            return None
        self._c_shed.labels(priority).inc()
        if self.flightrec is not None:
            self.flightrec.note("shed", priority=priority,
                                predicted_ttft_s=pred, limit_s=limit)
        # how long until enough backlog drains that the prediction
        # would pass — the honest Retry-After, not a constant
        return max(1.0, pred - limit)

    def _expire_due(self) -> None:
        """Deadline scrub, run at every tick start. Expired waiters
        drop straight out of the queue (they never cost a prefill);
        expired runners force a FULL drain barrier first — their pages
        must not be reclaimed under an in-flight block's writes — then
        leave their decode slot. Either way the request finishes
        state="expired" and its waiter is answered (the server turns
        that into the 504)."""
        now = time.monotonic()
        for req in [r for r in self.waiting
                    if r.deadline_s is not None and now >= r.deadline_s]:
            self.waiting.remove(req)
            self._expire(req, "waiting")
        live = [r for r in self._all_live
                if r.deadline_s is not None and now >= r.deadline_s]
        if live:
            self._drain_inflight("expired")
            for req in live:
                if not req.done:  # the drain may have finished it
                    self._expire(req, "running")

    def _expire(self, req: Request, where: str) -> None:
        req.expired_where = where
        self._c_deadline.labels(where).inc()
        if self.flightrec is not None:
            self.flightrec.note("deadline_504", id=req.id, where=where,
                                tokens=len(req.output))
        self._finish(req, state="expired")

    def cancel(self, req: Request) -> None:
        """Abort a request (e.g. client disconnect): frees slot + pages.

        With decode blocks in flight a FULL drain barrier runs first:
        the blocks were dispatched with this request's slot live, and
        its pages must not be reclaimed (and possibly handed to a later
        admission) while device writes to them are still outstanding."""
        if req.done:
            return
        if req.slot is not None and (self._inflight or self._pending_first):
            self._drain_inflight("cancel")
            if req.done:
                return  # the drain surfaced a natural finish
        if req in self.waiting:
            self.waiting.remove(req)
        self._finish(req, state="cancelled")

    @property
    def _all_live(self) -> List[Request]:
        return (list(self.running) + list(self._prefill_group)
                + list(self._sp_group))

    def unfinished_requests(self) -> List[Request]:
        """Every request that would be lost in a crash: running,
        mid-chunked-prefill, and waiting — the set a serving snapshot
        (ckpt.sharded.save_serving_snapshot) must persist."""
        return self._all_live + list(self.waiting)

    def abort_all(self) -> None:
        """Wedge-path drain: host-only bookkeeping, NO device calls (the
        device may be the thing that's broken). Every waiter's on_finish
        fires; slots/pages are reclaimed in host state only."""
        # never block on a possibly-wedged device
        self._inflight = []
        self._pending_first = []
        self._pending_first_keys.clear()
        self._flush_counts = []  # device scalars: dropped unread
        self._spec_rem = None
        self._starved_by = None  # nothing left to serve: not starved
        # staged-but-unflushed window K/V is DROPPED, not flushed (no
        # device calls here): every owning request is being cancelled,
        # and dropping resets the staged count so a later flush can
        # never scatter stale entries into reclaimed pages
        self.engine.drop_kv_window()
        self._plen_host[:] = 0  # mixed carries: every slot decode-phase
        self._epoch += 1  # cached decode operands are now stale
        for req in self.unfinished_requests():
            req.state = "cancelled"
            req.t_finish = time.monotonic()
            if self.trace is not None:
                self.trace.event(req.id, "finish", state="cancelled",
                                 reason="abort_all",
                                 tokens=len(req.output))
            if req.slot is not None:
                self.alloc.release(req.slot)
                self.slots[req.slot] = None
                req.slot = None
            if req.on_finish is not None:
                try:
                    req.on_finish(req)
                except Exception:
                    pass
        self.running.clear()
        self.waiting.clear()
        self._prefill_group.clear()
        self._sp_group.clear()

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self._prefill_group
                    or self._sp_group)

    def run_until_done(self, max_ticks: int = 100000) -> None:
        for _ in range(max_ticks):
            if not self.has_work:
                return
            self.tick()
        raise RuntimeError("scheduler did not drain")

    def tick(self) -> int:
        """One scheduling round: lazy drain, inline admission, then a
        dispatch-ahead fused block.

        Up to `RuntimeConfig.inflight_blocks` fused blocks stay in
        flight: block t+1 chains on block t's
        device-resident carry BEFORE t is drained, so this tick's host
        section — drain bookkeeping, admission, operand assembly —
        overlaps the device computing earlier blocks instead of idling
        it. Draining is lazy: only the
        oldest block is fetched, and only once the in-flight queue is
        full. A lazy drain waits for the block it drains and for
        nothing newer: it reads that block's own output arrays,
        launches no program to read them, and leaves the count of the
        flush it dispatches to a later drain, so its fetch returns
        while the newer block still runs and the rest of the tick
        hides behind that block (drain_overlap_total counts how
        often). A FULL barrier
        (everything drained) runs only when host and device state must
        reconcile:

        * under speculation and with the seq-parallel lane only: a
          finish surfaced at a lazy drain — the speculative budget
          carry (_spec_rem) is reset to host truth there, and the
          lane donates the pool binding in dispatches of its own
          (each of which drains first: cause `sp_prefill`);
        * page pressure (_ensure_or_preempt) — preemption must never
          reclaim pages a dispatched block still writes;
        * cancel() and an expired deadline — same hazard, external
          trigger: the request's lane is LIVE in the blocks in flight.

        Admission is none of them: it is host bookkeeping and carry
        edits between dispatches (_admit_inline), and the prompt rides
        the next block's chunks.

        Without speculation and without the lane (_finish_inline) a
        finish that surfaces at a lazy drain is taken THERE, with the
        newer blocks still in flight: the freed slot and pages are
        visible to this tick's admission, page preallocation and
        operand assembly as they were behind the barrier, and the
        block this tick dispatches chains on the newest block in
        flight. Nothing on that list needs the newer blocks on the
        host, because a request that has finished is DEAD in every
        newer block from its first step:

        * it ended by its budget: every newer block was given budget 0
          for its slot (_mixed_block: base less the estimates of the
          blocks in flight, which are exact up to a stop token, and a
          request that spent its budget met none), or it ended by its
          stop token: its chain token is frozen at the stop id, and
          _packed_scan starts a decode-phase lane whose token is its
          stop id dead. A dead lane advances no length, stages nothing
          and writes the null page;
        * the flush that precedes the emission was dispatched before
          _finish released a page (_drain_blocks) and runs after every
          block in flight: the old owner's staged rows land before
          anything the page's next owner writes, and before a prefix
          hit on a page the finish registered is read;
        * reset_slot and _seed_mixed_slot edit the CURRENT carry
          bindings, the newest block's results, so they land after it;
          the block table is a host mirror pushed once a dispatch, and
          the blocks in flight hold the table they were given;
        * the drain of a newer block discards the tokens of a lane
          whose snapshot names a request that is done (or another
          generation), and _mixed_block counts a newer block's
          emission estimate only for the request it was made for: the
          next request of the slot starts with its own budget.

        Speculative mode (speculative_gamma > 0) runs the SAME pipeline
        with the speculative mixed block: drafts come from the
        device-resident token history, acceptance is computed
        inside the scan, and the chained carry is (history, lengths,
        remaining budgets) instead of the final-token vector — no
        barrier per round.

        Returns the number of tokens generated this round (throughput
        accounting for the serve loop)."""
        before = self._c_tokens.value
        # tick-anatomy reset: zero the phase accumulator (the spans
        # below add their exclusive monotonic deltas), clear the
        # barrier-cause list, zero the fetch wait. The time since the
        # last tick's end (lock, wake, profile poll) is this tick's
        # gap, and the starvation clock's `outside_tick` if it runs.
        t_tick0 = self._tick_t0 = time.monotonic()
        self._tick_gap = t_tick0 - self._span_t
        self._lap(t_tick0, None)
        self._span_name = "other"
        for p in TICK_PHASES:
            self._tick_phases[p] = 0.0
        self._span_cpu = self._tick_cpu0 = time.thread_time()
        self._tick_delay0 = run_delay_s()
        self._cpu_on = self.ticklog.next_seq % self._cpu_period == 0
        if self._cpu_on:
            t0 = time.monotonic()
            self._tick_proc0 = time.process_time()
            self._proc_cost = time.monotonic() - t0
        self._tick_wall_by = {}
        self._tick_off_cpu = {}
        self._tick_stall = None
        self._tick_starved = self._tick_starved_cause = None
        self._tick_starved_by = {}
        self._tick_causes = []
        self._tick_finishes_inline = 0
        self._tick_fetch = 0.0
        self._tick_overlapped = None
        self._tick_expert_loads = []
        self._tick_ssm = None
        self._tick_latent = None
        self._tick_share = None
        self._tick_hc = None
        self._tick_swa = None
        blocks0 = self.engine.blocks_launched
        with TraceAnnotation("bf.tick", seq=self.ticklog.next_seq,
                             batch=len(self.running),
                             waiting=len(self.waiting)):
            self._tick_sections()
            made = int(self._c_tokens.value - before)
            self._record_tick(t_tick0, made, blocks0)
        return made

    def _tick_sections(self) -> None:
        """The tick's sections in order, each under its span."""
        rt = self.engine.runtime
        spec = self._spec_mode
        k = max(1, rt.decode_steps_per_tick)
        depth = max(1, rt.inflight_blocks)
        # the newest block in flight is done before the tick has done
        # anything: the device ran dry while the host was elsewhere
        if self._inflight and _device_ready(self._inflight[-1][1]):
            self._starve("late_tick")
        # deadline scrub first: an expired request must not survive
        # into this tick's admission or decode dispatch (a drain it
        # forces accrues to drain_barrier, not to expire)
        with self._span("expire"):
            self._expire_due()
        # lazy drain: consume the oldest block once the queue is full
        # (depth=1 degenerates to a drain every tick). A
        # finish surfacing there is taken there without speculation
        # and without the seq-parallel lane (the newer blocks stay in
        # flight: the finished lane is dead in them); with either it
        # is a membership change -> full barrier.
        while len(self._inflight) >= depth:
            finished = self._drain_oldest()
            if not finished:
                continue
            if self._finish_inline:
                self._c_finish_inline.inc(finished)
                self._tick_finishes_inline += finished
            else:
                self._drain_inflight("finish")
        with self._span("admit"):
            # seq-parallel long-prompt lane (ISSUE 20): at most one
            # chunk per tick — the lane's dispatch donates the pool
            # binding, so _sp_prefill_step drains in-flight blocks itself
            if self._sp_enabled:
                self._sp_admit()
                self._sp_prefill_step()
            # admission is a host-side carry edit between dispatches,
            # no barrier: the prompt rides the next fused block
            self._admit_inline()
        if self.running:
            self._h_batch.observe(len(self.running))
        # Preallocate pages for every step still in flight PLUS this
        # block up front: device lengths run ahead of the host mirror
        # by up to `step` tokens per undrained block (k samples for a
        # plain block, k rounds x (gamma+1) emissions for a spec
        # block), so the horizon is (inflight+1)*step + 1 (chain token
        # + the new samples) — and the block table dirties (syncs to
        # the device) at most once per TICK. Any more would
        # add spurious page pressure in a tight pool; under pressure
        # _ensure_or_preempt falls back to a drain barrier before it
        # ever preempts. A spec verify's trailing writes past the
        # lifetime clamp land on the null page via the table default.
        step = k * self.engine.spec_emit_width if spec else k
        horizon = (len(self._inflight) + 1) * step + 1
        for req in list(self.running):
            if req in self.running:
                need = min(len(req.all_tokens) + horizon,
                           len(req.prompt) + req.max_new_tokens)
                self._ensure_or_preempt(req, need)
        if self._prefill_group:
            # prefill lanes advance up to C tokens per scan step, so
            # their device write horizon is k*C per undrained block
            pf_h = (len(self._inflight) + 1) * k * self._mixed_chunk + 1
            for req in list(self._prefill_group):
                if req in self._prefill_group:
                    need = min(len(req.all_tokens) + pf_h,
                               len(req.prompt) + req.max_new_tokens)
                    self._ensure_or_preempt(req, need)
        # the fused block covers both phases: its dispatch section
        # gets its own phase label so tick anatomy stays honest about
        # where admission+prefill time went
        with self._span("mixed"):
            dispatched = self._mixed_block(k)
        if not dispatched and (self._inflight or self._pending_first):
            # nothing dispatchable (every budget is spent on device):
            # the remaining tokens exist only in flight — fetch them
            # now or the loop would spin forever. In spec mode this is
            # the budget-carry reconciliation (only the device knows
            # the remainders), hence the distinct cause label.
            self._drain_inflight("spec" if spec else "idle")
        self._g_inflight.set(len(self._inflight))

    def _record_tick(self, t_tick0: float, made: int, blocks0: int) -> None:
        """Close the tick's anatomy record: charge the tick's own
        exclusive time to "other" (untimed host work — page prealloc,
        trace appends), feed the per-phase histograms, the host/device
        split, the timeline ring, and the flight-recorder trigger
        poll. Host arithmetic only — no device value is ever touched
        here."""
        tp = self._tick_phases
        now, cpu = time.monotonic(), time.thread_time()
        self._lap(now, cpu if self._cpu_on else None)
        self._span_name = "outside_tick"
        if not self.has_work:
            # an empty server is not starved
            self._starved_by = None
        wall = now - t_tick0
        blocks = self.engine.blocks_launched
        acct, figures = self._account(cpu)
        sound = self._sound_ticks
        if wall > STALL_MIN_S and sound and self._tick_stall is None:
            usual = statistics.median(t[0] for t in sound)
            if wall > STALL_FACTOR * usual:
                over = self._tick_overlapped
                self._note_stall(wall - usual, *self._held(), acct,
                                 None if over is None else not over)
        if blocks > blocks0 and not self._compiled():
            sound.append((wall, acct["cpu_s"], dict(tp), self._tick_wall_by))
        self._tick_base = figures
        for name, h in self._h_phase.items():
            h.observe(tp[name])
        fetch = min(self._tick_fetch, wall)
        self._t_device_total += fetch
        self._t_host_total += max(0.0, wall - fetch)
        loads = self._tick_expert_loads
        load = [float(sum(v[i] for v in loads)) / len(loads)
                for i in range(len(loads[0]))] if loads else None
        self.ticklog.record(wall, tp, fetch_s=fetch, expert_load=load,
                            kind_load=self._pages_by_kind(),
                            ssm_load=self._tick_ssm,
                            latent_load=self._tick_latent,
                            share_load=self._tick_share,
                            hc_load=self._tick_hc,
                            overlapped=self._tick_overlapped,
                            inflight=len(self._inflight),
                            barrier_causes=self._tick_causes,
                            finishes_inline=self._tick_finishes_inline,
                            batch=len(self.running),
                            waiting=len(self.waiting),
                            pages_free=self.alloc.free_pages,
                            generated=made, spec=self._spec_mode,
                            program=self.engine.last_program
                            if blocks > blocks0 else None,
                            rows=self.engine.last_rows
                            if blocks > blocks0 else None,
                            block=blocks, lock_s=self.loop_lock_s,
                            compiles=int(self._c_compiles.value),
                            starved_s=self._tick_starved,
                            starved_cause=self._tick_starved_cause,
                            starved_by=self._tick_starved_by,
                            gap_s=self._tick_gap, profiled=self.profiled,
                            off_cpu_by=self._tick_off_cpu
                            if self._cpu_on else None,
                            stall=self._tick_stall, **acct)
        self.loop_lock_s = 0.0
        if self.flightrec is not None:
            self.flightrec.poll({
                "slo_burn_rate": self._g_slo_burn.value,
                "preemptions_total": self._c_preempt.value,
                "deadline_expired_total": sum(
                    c.value for c in self._c_deadline._children.values()),
                "queue_depth": float(len(self.waiting)),
                "kv_pages_free": float(self.alloc.free_pages)})
        ts = self.timeseries
        if ts is not None and ts.due():
            gauges, rates = self._timeseries_signals()
            ts.sample(gauges, rates=rates, t_wall=time.time())

    def _pages_by_kind(self) -> Optional[List[float]]:
        """The tick record's `kind_load` for a cache by kind, None for
        every other: [rows read, rows whole] of the blocks the tick
        drained (None where it drained none), the pages held by kind
        and the rings' wraps since the last tick (a slot's written
        length over the ring's rows, against what it was)."""
        R = self._ring_pages
        if not R:
            return None
        rows = R * self.engine.cache.page_size
        held = {r.slot: self._written(r) // rows for r in self._all_live
                if r.slot is not None}
        wraps = sum(max(0, n - self._ring_wraps.get(s, 0))
                    for s, n in held.items())
        self._ring_wraps = held
        self._c_ring_wraps.inc(wraps)
        full = self.alloc.num_pages - self.alloc.free_pages
        self._g_pages_slide.set(float(R * len(held)))
        self._g_pages_full.set(float(full))
        return [*(self._tick_swa or (None, None)), R * len(held), full,
                wraps]

    def _process_figures(self) -> tuple:
        """The process's running figures every tick takes deltas of: the
        collections' seconds and their counts by generation, and the
        programs compiled (counters of the registry: no system call)."""
        return (self._c_gc_s.value, [c.value for c in self._c_gc_gen],
                self._c_compiles.value)

    def _account(self, cpu: float) -> tuple:
        """The tick record's fields of these names, which are also what
        a stall's cause is told by, and the figures they were taken
        from. Since the tick began: `cpu_s`, the tick thread's CPU
        seconds (`cpu`: its CPU clock now); `run_delay_s`, its time
        runnable with no CPU to run on (None where the kernel keeps
        none); and in a sampled tick `proc_cpu_s`, the CPU seconds of
        ALL the process's threads (None in the others): less `cpu_s`
        it is what the OTHER threads burned meanwhile (each a holder of
        the interpreter lock, or native code beside it). Since the LAST
        tick ended, the fraction of a millisecond between two ticks
        included: `gc_s`, `gc_collections` and `gc_generation` (the
        oldest generation examined, None where none ran), which count
        collections on any thread."""
        gc0, gens0, _ = self._tick_base
        figures = gc_s, gens, _ = self._process_figures()
        delay0, delay = self._tick_delay0, run_delay_s()
        ran = [int(b - a) for a, b in zip(gens0, gens)]
        proc = None
        if self._cpu_on:
            # the process's clock, and what its read costs with the
            # threads there are now (the cheaper of the tick's two
            # reads: one may have waited for a CPU), which says how
            # many ticks share the next sampled one
            t0 = time.monotonic()
            proc = time.process_time() - self._tick_proc0
            cost = min(self._proc_cost, time.monotonic() - t0)
            self._cpu_period = max(1, math.ceil(
                (SPAN_READS_A_TICK * self._thread_cost + 2 * cost)
                / CPU_CLOCK_BUDGET_S))
        return {"cpu_s": cpu - self._tick_cpu0, "proc_cpu_s": proc,
                "gc_s": gc_s - gc0, "gc_collections": sum(ran),
                "gc_generation": max((g for g, n in enumerate(ran) if n),
                                     default=None),
                "run_delay_s": None if delay is None or delay0 is None
                else delay - delay0}, figures

    def _compiled(self) -> bool:
        """A program was compiled since the last tick ended."""
        return self._c_compiles.value > self._tick_base[-1]

    def _held(self) -> tuple:
        """The TICK_PHASES name and the span's own name whose wall in
        the tick under way lies furthest over their medians in the sound
        ticks: where a stalled tick's excess sits."""
        past = self._sound_ticks

        def most(now: Dict[str, float], i: int) -> str:
            return max(now, key=lambda k: now[k] - statistics.median(
                t[i].get(k, 0.0) for t in past))
        return most(self._tick_phases, 2), most(self._tick_wall_by, 3)

    def _note_stall(self, excess: float, phase: str, span: str, acct: Dict,
                    newest_ready: Optional[bool],
                    fetch_s: Optional[float] = None) -> None:
        """The one writer of a stall: the tick record's `stall` and the
        flight recorder's note, one a tick. `excess` is the seconds over
        the usual (a tick's wall over the sound ticks' median, a fetch
        over the fetches'), `phase` and `span` where they sat, `acct`
        the account so far (_account). The cause is the first of these
        figures to cover half the excess: a compilation (any in the
        tick), collections, the tick thread runnable without a CPU, the
        process's other threads on CPUs (the interpreter lock's other
        holders; known in a sampled tick alone), the tick thread's own
        CPU beyond the usual; else the thread was `blocked`, off a CPU
        for none of those reasons: a system call, a lock, the runtime,
        the device."""
        half = excess / 2.0
        usual_cpu = statistics.median(
            t[1] for t in self._sound_ticks) if self._sound_ticks else 0.0
        if self._compiled():
            cause = "compile"
        elif acct["gc_s"] >= half:
            cause = "gc"
        elif (acct["run_delay_s"] or 0.0) >= half:
            cause = "descheduled"
        elif (acct["proc_cpu_s"] or 0.0) - acct["cpu_s"] >= half:
            cause = "other_threads"
        elif acct["cpu_s"] - usual_cpu >= half:
            cause = "on_cpu"
        else:
            cause = "blocked"
        self._tick_stall = {"phase": phase, "span": span, "cause": cause,
                            "excess_s": excess}
        if self.flightrec is not None:
            self.flightrec.note(
                "stall", tick=self.ticklog.next_seq, **self._tick_stall,
                wall_s=time.monotonic() - self._tick_t0,
                fetch_s=self._tick_fetch if fetch_s is None else fetch_s,
                newest_ready=newest_ready, profiled=self.profiled, **acct)

    def _timeseries_signals(self):
        """The SignalRecorder's per-interval snapshot (gauges, rates):
        cheap host reads off the registry + tick anatomy. `rates` maps
        OUTPUT signal name -> CUMULATIVE counter value — the recorder
        turns them into per-second deltas (Counter.rate, clamped at 0
        across resets). Runs only when the recorder is due, never per
        tick."""
        snap = self.registry.snapshot()
        gauges = {
            "queue_depth": float(len(self.waiting)),
            "active_requests": float(len(self._all_live)),
            "inflight_depth": float(len(self._inflight)),
            "kv_pages_free": float(self.alloc.free_pages),
            "slo_burn_rate": self._g_slo_burn.value,
        }
        if self.host_tier is not None:
            gauges["kv_tier_hit_rate"] = self._tier_hit_rate()
        total = self._t_host_total + self._t_device_total
        if total > 0.0:
            gauges["tick_host_frac"] = self._t_host_total / total
        pp = self.ticklog.phase_percentiles()
        if pp:
            gauges["tick_phase_dominant_p95"] = max(
                v["p95"] for k, v in pp.items() if k != "other")
        rates = {
            "tokens_per_sec": snap.get("tokens_generated_total", 0.0),
            "preemptions_per_sec": snap.get("preemptions_total", 0.0),
            "shed_per_sec": snap.get("shed_total", 0.0),
            "deadline_expired_per_sec":
                snap.get("deadline_expired_total", 0.0),
        }
        for cause, v in self.barrier_causes().items():
            rates[f"barrier_{cause}_per_sec"] = v
        return gauges, rates

    def metrics(self) -> Dict[str, float]:
        """Legacy flat-dict view, assembled from the typed registry.

        NB: the raw-gap ITL percentiles carry PER-TICK-BURST semantics
        under pipelined dispatch — gaps are stamped at the stacked
        drain, so they bimodalize (p50 ~ 0, p95 ~ tick) — and are
        therefore exposed ONLY under itl_p50/p95/max_tick_burst
        (ISSUE 10 satellite: the degenerate bare itl_p50/itl_p95 keys
        are gone). The ITL metrics of record are itl_req_mean_* and
        the registry's real histograms (ttft_seconds,
        itl_req_mean_seconds); see obs/metrics.py HELP.
        """
        m: Dict[str, float] = {
            "requests_total": self._c_requests.value,
            "requests_finished": self._c_finished.value,
            "tokens_generated_total": self._c_tokens.value,
            "preemptions_total": self._c_preempt.value,
            "spec_forwards_total": self._c_spec_fwd.value,
            "spec_drafts_accepted_total": self._c_spec_acc.value,
            # compat: the unlabeled sum over the {cause} family — the
            # key every pre-ISSUE-15 consumer (spec bench, tests) reads
            "drain_barriers_total": sum(self.barrier_causes().values()),
            "finishes_inline_total": self._c_finish_inline.value,
        }
        if self._spec_mode:
            fwd = self._c_spec_fwd.value
            m["spec_block_tokens_total"] = self._c_spec_tok.value
            # the speculation headline: tokens each verify forward paid
            # for (1.0 = speculation is earning nothing over plain
            # decode; > 1 = drafts are landing)
            m["spec_tokens_per_forward"] = \
                self._c_spec_tok.value / fwd if fwd else 0.0
            h = self._h_accept
            m["spec_accept_rate"] = \
                h._sum / h._count if h._count else 0.0
        m["queue_depth"] = len(self.waiting)
        m["active_requests"] = len(self._all_live)
        m["kv_pages_free"] = self.alloc.free_pages
        m["kv_pages_total"] = self.alloc.num_pages
        if hasattr(self.alloc, "hit_tokens"):
            m["prefix_cache_hit_tokens"] = self.alloc.hit_tokens
            m["prefix_cache_lookup_tokens"] = self.alloc.lookup_tokens
        if self.host_tier is not None:
            st = self.host_tier.stats()
            m["kv_tier_pages"] = st["entries"] + st["spilled_entries"]
            m["kv_tier_bytes"] = st["bytes"]
            m["kv_tier_pages_saved_total"] = st["saves"]
            m["kv_tier_pages_restored_total"] = st["restores"]
            m["kv_tier_misses_total"] = st["misses"]
            m["kv_tier_spills_total"] = st["spills"]
            m["kv_tier_hit_rate"] = self._tier_hit_rate()
            if self._tier_restores:
                a = np.asarray(self._tier_restores)
                m["kv_tier_restore_seconds_p50"] = \
                    float(np.percentile(a, 50))
                m["kv_tier_restore_seconds_p95"] = \
                    float(np.percentile(a, 95))
        if self._ttfts:
            a = np.asarray(self._ttfts)
            m["ttft_p50"] = float(np.percentile(a, 50))
            m["ttft_p95"] = float(np.percentile(a, 95))
        if self._itls:
            # raw-gap percentiles carry per-tick-burst semantics under
            # pipelined dispatch (p50 is identically 0.0 between
            # burst-mates at decode_steps_per_tick > 1 — the r05
            # headline artifact), so they are ONLY exposed under the
            # explicit _tick_burst suffix; itl_req_mean_* is the ITL
            # metric of record
            a = np.asarray(self._itls)
            m["itl_p50_tick_burst"] = float(np.percentile(a, 50))
            m["itl_p95_tick_burst"] = float(np.percentile(a, 95))
            m["itl_max_tick_burst"] = float(a.max())
        if self._itl_means:
            a = np.asarray(self._itl_means)
            m["itl_req_mean_p50"] = float(np.percentile(a, 50))
            m["itl_req_mean_p95"] = float(np.percentile(a, 95))
        m["inflight_depth"] = float(self._g_inflight.value)
        m["deadline_expired_total"] = sum(
            c.value for c in self._c_deadline._children.values())
        m["shed_total"] = sum(
            c.value for c in self._c_shed._children.values())
        if self.slo_ttft_s is not None or self.slo_itl_s is not None:
            viol = sum(c.value for c in
                       self._c_slo_viol._children.values())
            ok = self._c_slo_ttft_ok.value + self._c_slo_itl_ok.value
            m["slo_ttft_ok_total"] = self._c_slo_ttft_ok.value
            m["slo_itl_ok_total"] = self._c_slo_itl_ok.value
            m["slo_violations_total"] = viol
            m["slo_burn_rate"] = self._g_slo_burn.value
            m["slo_attainment"] = ok / (ok + viol) if ok + viol else 1.0
        if self._kv_flushes:
            # write-combined KV window flush (kv_write_combine): host
            # wall per drain-time flush dispatch + tokens landed per
            # flush — the two numbers that say what one pool scatter
            # per drain costs and how much write combining it bought
            a = np.asarray(self._kv_flushes)
            m["kv_flush_p50"] = float(np.percentile(a, 50))
            m["kv_flush_p95"] = float(np.percentile(a, 95))
            m["kv_window_tokens_flushed_total"] = \
                self._c_kv_flushed.value
        # tick anatomy (ISSUE 15): per-phase p50/p95 over the timeline
        # ring window ("drain" = lazy + barrier drains combined — the
        # bench headline set), the host/device wall split, and the
        # dominant phase's p95 (the autoscale gauge: a host-bound
        # replica shows a fat admit/dispatch/drain phase, a
        # device-bound one a fat fetch share)
        pp = self.ticklog.phase_percentiles()
        for name in ("drain", "admit", "assemble", "dispatch",
                     "mixed", "expire", "spec_emit", "flush"):
            if name in pp:
                m[f"tick_phase_{name}_p50"] = pp[name]["p50"]
                m[f"tick_phase_{name}_p95"] = pp[name]["p95"]
        if pp:
            m["tick_phase_dominant_p95"] = max(
                v["p95"] for k, v in pp.items() if k != "other")
        total = self._t_host_total + self._t_device_total
        if total > 0:
            m["tick_host_frac"] = self._t_host_total / total
            m["tick_device_frac"] = self._t_device_total / total
        # prompt tokens that rode fused mixed blocks (ISSUE 18): all
        # prefill work but the seq-parallel lane's
        m["mixed_dispatch_prefill_tokens_inline"] = \
            self._c_chunk_tokens.value
        if self._sp_enabled:
            m["seq_parallel_prefill_tokens_total"] = \
                self._c_sp_tokens.value
        return m

    def barrier_causes(self) -> Dict[str, float]:
        """Per-cause FULL-barrier counts: the drain_barriers_total
        {cause=} family as a plain dict (which membership-change class
        is costing the pipeline)."""
        fam = self._c_barriers
        with fam._lock:
            items = list(fam._children.items())
        return {vals[0]: child.value for vals, child in items}

    # -- host KV tier hooks (cache/hosttier.py) ------------------------------

    def _tier_hit_rate(self) -> float:
        st = self.host_tier
        lookups = st.restores + st.misses
        return st.restores / lookups if lookups else 0.0

    def _tier_save(self, h: bytes, pid: int) -> None:
        """Allocator on_evict hook: demote the recycled page's bytes to
        the host tier. The page is registered (content-immutable) until
        this very moment, so the gather reads stable bytes; read_pages
        flushes the write-combined window itself if it is dirty. The
        allocator swallows exceptions — a failed demotion costs a
        future prefill, never correctness."""
        k, v, ks, vs = self.engine.read_pages([pid])
        self.host_tier.save(h, k[:, 0], v[:, 0],
                            None if ks is None else ks[:, 0],
                            None if vs is None else vs[:, 0])
        self._c_tier_saved.inc()

    def _tier_revive(self, h: bytes) -> Optional[int]:
        """Allocator reviver hook: on a registry miss during admission's
        prefix walk, pull the chain's next page back from the host tier
        into a freshly claimed page. Returns the page id (the walk
        continues as a normal prefix hit) or None on a tier miss /
        page exhaustion (the admission prefills the tail itself)."""
        t0 = time.monotonic()
        data = self.host_tier.load(h)
        if data is None:
            self._c_tier_miss.inc()
            self._g_tier_hit.set(self._tier_hit_rate())
            return None
        try:
            pid = self.alloc.import_page(h)
        except MemoryError:
            return None  # every page held by a live slot: no revive
        if pid is None:
            # digest already registered (idempotent re-import shape):
            # serve the walk from the live entry
            return self.alloc.lookup(h)
        k, v, ks, vs = data
        self.engine.write_pages(
            [pid], k[:, None], v[:, None],
            None if ks is None else ks[:, None],
            None if vs is None else vs[:, None])
        dt = time.monotonic() - t0
        self._h_tier_restore.observe(dt)
        self._tier_restores.append(dt)
        self._c_tier_restored.inc()
        self._g_tier_hit.set(self._tier_hit_rate())
        return pid

    # -- internals ----------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def _sp_qualifies(self, req: Request) -> bool:
        """Does this prompt belong to the seq-parallel long-prompt
        lane? (The normal admission loops break on a qualifying head
        so the lane keeps FCFS order — a long prompt waits for the
        lane, it never falls back to a single-device prefill.)"""
        return (self._sp_enabled and len(req.all_tokens)
                > self.engine.runtime.seq_parallel_threshold)

    def _sp_admit(self) -> None:
        """Admit the head-of-queue request into the seq-parallel lane
        when it qualifies and the lane is empty: pages for the WHOLE
        prompt (+1 for the first decode token) are allocated up front —
        every chunk scatters straight into the pool, so there is no
        later growth point mid-prefill."""
        if not self._sp_enabled or self._sp_group or not self.waiting:
            return
        req = self.waiting[0]
        if not self._sp_qualifies(req):
            return
        slot = self._free_slot()
        if slot is None:
            return
        if self._shares_inflight_prefix(req):
            return  # defer: a prefilling member is writing req's prefix
        cached = self.alloc.admit(slot, req.all_tokens,
                                  len(req.all_tokens) + 1)
        if cached is None:
            return  # pool exhausted; decode will free/preempt
        self.waiting.popleft()
        req.slot, req.state = slot, "prefilling"
        req.prefilled = req.cached_at_admit = cached
        self.slots[slot] = req
        self._sp_group.append(req)
        self.engine.set_table_row(slot, self.alloc.pages_of(slot))
        self._epoch += 1  # membership changed: operands rebuild
        wait = time.monotonic() - req.t_enqueued
        self._h_queue_wait.observe(wait)
        if self.flightrec is not None:
            self.flightrec.note("admit", id=req.id, slot=slot,
                                queue_wait_s=wait, cached=cached,
                                seq_parallel=True)
        if self.trace is not None:
            self.trace.event(req.id, "admit", slot=slot,
                             queue_wait_s=wait,
                             prefix_cache_hit_tokens=cached,
                             resumed=req.preemptions > 0,
                             seq_parallel=True)

    def _sp_prefill_step(self) -> None:
        """Dispatch ONE seq-parallel prefill chunk for the lane's
        request (engine.sp_prefill_chunk), if it has one.

        The chunk program donates the newest pool binding, so any
        in-flight blocks drain first (the donation barrier). On
        completion the request leaves through
        _finish_prefill: pages publish to the
        prefix registry and the first token samples from the chunk's
        last-position logits."""
        if not self._sp_group:
            return
        req = self._sp_group[0]
        if self._inflight or self._pending_first:
            self._drain_inflight("sp_prefill")
            if req.done or req.slot is None:
                return  # the drain finished or preempted it
        toks = req.all_tokens
        chunk = toks[req.prefilled:req.prefilled + self._sp_chunk]
        if not chunk:
            return
        if self.trace is not None:
            self.trace.event(req.id, "sp_prefill_chunk",
                             start=req.prefilled, tokens=len(chunk),
                             degree=self.engine.sp_degree)
        logits = self.engine.sp_prefill_chunk(req.slot, chunk,
                                              req.prefilled)
        req.prefilled += len(chunk)
        self._c_sp_tokens.inc(len(chunk))
        if req.prefilled >= len(toks):
            # logits is [V] — _finish_prefill samples from [M, V] rows
            self._finish_prefill([req], logits[None, :])
            # mixed carries: the slot enters decode phase (plen 0); its
            # pool length was set by the chunk dispatches themselves
            self._plen_host[req.slot] = 0

    def _admit_inline(self) -> None:
        """Admission: pull waiting requests
        into free slots WITHOUT a drain barrier or a separate prefill
        dispatch — the prompt rides the next fused block's prefill
        lanes. Admission here is pure host bookkeeping plus per-slot
        device carry edits between dispatches (_seed_mixed_slot, the
        established reset_slot pattern: ``.at[slot].set`` on arrays
        in-flight blocks never touch for a free slot, nor for the
        slot of a request that finished at this tick's lazy drain: its
        lane is dead in them).

        The concurrent-prefill cap (_mixed_max_pf, derived from
        RuntimeConfig.prefill_inline_budget) bounds how many slots may
        be in prefill phase at once (_prefilling_ahead) — with chunk
        width C per slot per scan step, at most ~prefill_inline_budget
        prompt tokens are chewed per step while decode slots wait on
        that step's forward. That bound IS the ITL-tail knob."""
        admitted = False
        while (self.waiting
               and self._prefilling_ahead() < self._mixed_max_pf):
            slot = self._free_slot()
            if slot is None:
                break
            req = self.waiting[0]
            if self._sp_qualifies(req):
                break  # long prompt: waits for the seq-parallel lane
            if self._shares_inflight_prefix(req):
                break  # defer: a prefilling member is writing req's prefix
            cached = self.alloc.admit(slot, req.all_tokens,
                                      len(req.all_tokens) + 1)
            if cached is None:
                break  # pool exhausted; decode will free/preempt
            self.waiting.popleft()
            req.slot, req.state = slot, "prefilling"
            req.prefilled = req.cached_at_admit = cached
            self.slots[slot] = req
            self._prefill_group.append(req)
            self.engine.set_table_row(slot, self.alloc.pages_of(slot))
            with self._span("admit.seed"):
                self._seed_mixed_slot(req)
            admitted = True
            wait = time.monotonic() - req.t_enqueued
            self._h_queue_wait.observe(wait)
            if self.flightrec is not None:
                self.flightrec.note("admit", id=req.id, slot=slot,
                                    queue_wait_s=wait, cached=cached)
            if self.trace is not None:
                self.trace.event(req.id, "admit", slot=slot,
                                 queue_wait_s=wait,
                                 prefix_cache_hit_tokens=cached,
                                 resumed=req.preemptions > 0)
        if admitted:
            self._epoch += 1  # membership changed: operands rebuild

    def _prefilling_ahead(self) -> int:
        """Slots that will be in prefill phase when the NEXT block
        runs: members of the prefill group whose prompt the blocks
        dispatched so far do not finish (the lockstep simulation moved
        `prefilled` at dispatch). A member whose last chunk is in
        flight stays in the group until that block drains, but on the
        device it decodes from then on: it needs no chunk and holds no
        place under the cap, so the next request is admitted a tick
        sooner and a block behind a finished prompt is a decode block."""
        return sum(r.prefilled < self._plen_host[r.slot]
                   for r in self._prefill_group)

    def _seed_mixed_slot(self, req: Request) -> None:
        """Device-carry seeding for one mixed-dispatch admission. Every
        write is an ``.at[slot].set`` on the CURRENT carry binding —
        i.e. on the result of the newest in-flight block — so it lands
        after that block in device program order. In every in-flight
        block's snapshot the slot is free OR dead: free (an inactive
        lane), or held by a request whose finish a lazy drain has just
        taken with that block still in flight, whose lane started the
        block dead (tick()'s docstring: budget 0, or its chain token
        frozen at its stop id). Either lane advances nothing and its
        writes land on the null page, so nothing here races a
        dispatched program. The dead request's chain token stays in
        _next_dev[slot]; a new request with the SAME stop id does not
        start dead, because _packed_scan skips the chain-token check
        for a slot in prefill phase, and a seeded slot is in prefill
        phase (the cached prefix is always shorter than the prompt).

        Seeds: pool lengths at the cached prefix (the warm-prefix
        contract), window count at zero, the chunk cursor at the
        cached prefix, and the prompt tokens — into the prompt-buffer
        row (plain mixed) or the token-history row (spec mixed, where
        history doubles as the prompt buffer and the budget injects
        into the device remainder carry when one is live)."""
        eng = self.engine
        slot, toks = req.slot, req.all_tokens
        cached = req.cached_at_admit
        with eng._mesh_ctx():
            eng.cache = eng.cache._replace(
                lengths=eng.cache.lengths.at[slot].set(cached))
            if eng._win_len is not None:
                eng._win_len = eng._win_len.at[slot].set(0)
            if self._cursor_dev is None:
                self._cursor_dev = eng.carry(
                    np.zeros((eng.num_slots,), np.int32))
            self._cursor_dev = self._cursor_dev.at[slot].set(cached)
            self._plen_host[slot] = len(toks)
            if self._spec_mode:
                row = np.zeros((self._hist_dev.shape[1],), np.int32)
                row[:len(toks)] = toks
                self._hist_dev = self._hist_dev.at[slot].set(
                    jnp.asarray(row))
                self._hist_len_dev = self._hist_len_dev.at[slot].set(
                    len(toks))
                if self._spec_rem is not None:
                    self._spec_rem = self._spec_rem.at[slot].set(
                        req.max_new_tokens - len(req.output))
            else:
                if self._pbuf_dev is None:
                    self._pbuf_dev = jnp.zeros(
                        (eng.num_slots, eng.cache.max_seq), jnp.int32)
                row = np.zeros((self._pbuf_dev.shape[1],), np.int32)
                row[:len(toks)] = toks
                self._pbuf_dev = self._pbuf_dev.at[slot].set(
                    jnp.asarray(row))

    def _shares_inflight_prefix(self, req: Request) -> bool:
        """Prefix caching only: would `req` hit KV pages a member of the
        prefill group is still writing? Admitted beside it, req would
        pay the shared prefix's prefill a second time. So if req's
        leading full block chain-matches a member's, its admission
        waits — the member registers its pages when its prompt
        completes and req then admits with a cache hit. FIFO order is
        preserved (admission simply stops for the tick)."""
        if not self.engine.runtime.prefix_caching or not self._prefill_group:
            return False
        from butterfly_tpu.cache.prefix import chain_block_hashes
        ps = self.alloc.page_size
        head = chain_block_hashes(req.all_tokens, ps, 1)
        if not head:  # shorter than one block: nothing cacheable
            return False
        return any(chain_block_hashes(m.all_tokens, ps, 1) == head
                   for m in self._prefill_group)

    def _finish_prefill(self, reqs: List[Request], logits) -> None:
        """The seq-parallel lane's members whose prompt is now fully in
        cache (a prompt that rode a block's chunks leaves through
        _mixed_transitions): publish pages for
        prefix reuse (no-op without prefix caching), sample every
        member's first token ON DEVICE from the dispatch's logits
        [M, V] in one vectorized draw, start decoding. Tokens are
        fetched at the next stacked drain; even a max_new==1 request
        keeps its slot until then (its extra decode steps are discarded
        like any post-finish in-flight work)."""
        for req in reqs:
            self.alloc.register(req.slot, req.all_tokens)
            self._sp_group.remove(req)
            req.state = "running"
            self.running.append(req)
            ran = len(req.all_tokens) - req.cached_at_admit
            self._h_prefill_tokens.observe(ran)
            if self.trace is not None:
                self.trace.event(req.id, "prefill_done", tokens=ran,
                                 total=len(req.all_tokens))
        self._key, sub = jax.random.split(self._key)
        firsts = sample_batched(
            logits, sub,
            np.asarray([r.temperature for r in reqs], np.float32),
            self.engine.runtime_top_k, self.engine.runtime_top_p)
        base = self._next_dev if self._next_dev is not None \
            else self.engine.carry(self._next_tokens)
        slots_arr = np.asarray([r.slot for r in reqs], np.int32)
        self._next_dev = base.at[slots_arr].set(firsts)
        if self._spec_mode:
            # seed the device-side token history the on-device drafter
            # reads: the full prompt (+ prior output on readmission)
            # from the host, plus the device-resident first token —
            # no host sync, the spec block chains on this carry
            H = self._hist_dev.shape[1]
            rows = np.zeros((len(reqs), H), np.int32)
            lens = np.zeros((len(reqs),), np.int32)
            for i, req in enumerate(reqs):
                toks = req.all_tokens
                rows[i, :len(toks)] = toks
                lens[i] = len(toks)
            self._hist_dev = self._hist_dev.at[slots_arr].set(
                jnp.asarray(rows)).at[slots_arr, lens].set(firsts)
            self._hist_len_dev = self._hist_len_dev.at[slots_arr].set(
                jnp.asarray(lens + 1))
        for i, req in enumerate(reqs):
            self._pending_first.append(
                (req, req.preemptions, req.slot, firsts[i]))
            self._pending_first_keys.add((req.id, req.preemptions))
        self._epoch += 1  # running set + pending-first set changed

    def _assemble(self) -> tuple:
        """Per-block host operands — the active/temps/stops/base-budget
        /spec-mask arrays and the slot snapshot — cached on the batch-
        membership epoch: back-to-back blocks over an unchanged batch
        skip the per-slot Python rebuild and the np.asarray churn.

        The batch is the running set and the prefill group:
        their lanes ride the same block (phase decided on device by
        cursor < plen), and their budget is the full remaining
        emission allowance (output is empty unless resumed from a
        preemption)."""
        if self._operands_epoch != self._epoch:
            with self._span("assemble"):
                S = self.engine.num_slots
                active = np.zeros((S,), bool)
                temps = np.zeros((S,), np.float32)
                stops = np.full((S,), -1, np.int32)
                base = np.zeros((S,), np.int32)
                specm = np.zeros((S,), bool)
                # seq-parallel-lane members never ride a block: their
                # prefill happens in dedicated sp_prefill_chunk dispatches
                # and they enter `running` only via _finish_prefill.
                batch = list(self.running) + list(self._prefill_group)
                for req in batch:
                    active[req.slot] = True
                    temps[req.slot] = req.temperature
                    stops[req.slot] = req.stop_token
                    specm[req.slot] = req.speculative
                    # tokens the request may still emit: max_new minus what
                    # the host has drained, minus an undrained
                    # admission-time first token (queued in _pending_first;
                    # set lookup — the old per-runner linear scan over the
                    # pending list was O(running x pending) every block)
                    pending = (req.id,
                               req.preemptions) in self._pending_first_keys
                    base[req.slot] = (req.max_new_tokens - len(req.output)
                                      - int(pending))
                self._operands = (active, temps, stops, base, specm,
                                  {req.slot: (req, req.preemptions)
                                   for req in batch})
                self._operands_epoch = self._epoch
        return self._operands

    def _enqueue_block(self, kind: str, carry, outs, k: int,
                       snapshot: Dict, *mixed) -> None:
        """A dispatched block joins the in-flight queue: (kind, chain
        carry, emission outputs, steps, slot snapshot, dispatch time,
        and for the mixed kinds the completing slots and the emission
        estimate)."""
        self._inflight.append((kind, carry, outs, k, snapshot,
                               time.monotonic(), *mixed))

    def _mixed_block(self, k: int) -> bool:
        """Dispatch ONE fused MIXED block (ISSUE 18): decode (or spec)
        lanes and prefill lanes ride the same k-step jitted program
        (engine.mixed_block_async / mixed_spec_block_async), chained
        on the previous block's device-resident carries — the previous
        block need NOT be drained first (dispatch-ahead) — one
        dispatch per tick covering both phases. Host work — operand
        assembly (_assemble), the RNG split, the dispatch itself — is
        paid once per BLOCK instead of once per token. Page growth
        happened at tick start.

        Per-slot stop ids and remaining-token budgets ride into the
        scan so a slot that finishes mid-block is masked ON DEVICE
        (lengths freeze, writes land on the null page) rather than
        generating garbage the drain discards; a finished slot's chain
        token stays frozen at its stop id, so every later in-flight
        block starts it dead too.

        The host runs a cheap lockstep simulation of each prefill
        lane's cursor: chunk progress is deterministic while a lane is
        live (a prefilling lane cannot die mid-prompt — its first
        possible emission is the completion-sampled first token), so
        ``req.prefilled`` advances to the block's post-state at
        DISPATCH time and the completion set rides the in-flight entry
        for drain-time state transitions (_mixed_transitions). For
        plain mixed the same simulation also yields per-slot emission
        counts, the budget look-ahead chained dispatches subtract
        (stop-deaths make it an over-estimate, which is safe: a slot
        that went dead early consumed less, but its chain token is
        frozen at its stop id, so under-budgeting it cannot drop real
        tokens).

        Spec mixed budgets: the first dispatch after a full barrier
        seeds the device budget vector from exact host state (base,
        minus nothing — the barrier drained every in-flight token);
        chained dispatches thread the previous block's device-resident
        remainder through, because a spec block's consumption is
        variable (1..gamma+1 tokens per live slot per round) and only
        the device knows it before the drain. Membership changes force
        a barrier anyway, so the carry is always exact.

        Returns True iff a block was dispatched."""
        if not (self.running or self._prefill_group):
            return False
        active, temps, stops, base, specm, snapshot = self._assemble()
        S = self.engine.num_slots
        self._key, sub = jax.random.split(self._key)
        plen = self._plen_host
        cursor = self._cursor_dev if self._cursor_dev is not None \
            else self.engine.carry(np.zeros((S,), np.int32))
        if self._spec_mode:
            C = self._mixed_chunk  # gamma + 1: the verify shape
            if self._spec_rem is None:
                if not (active & (base > 0)).any():
                    return False  # everything already emitted (undrained)
                budgets = base
            else:
                # device carry: exact remainder after every in-flight
                # round. The host cannot cheaply inspect it; dispatching a
                # potentially-empty block is safe — each tick still drains
                # the oldest block, so finishes keep surfacing and the
                # barrier-on-finish resets the carry to host truth.
                budgets = self._spec_rem
            # deterministic cursor advance: C prompt tokens per round
            # while mid-prefill (emissions can't kill the lane first)
            pf_done = []
            self._c_chunk_offered.inc(k * C * self._prefilling_ahead())
            for req in list(self._prefill_group):
                p = int(plen[req.slot])
                if req.prefilled < p:
                    adv = min(p, req.prefilled + k * C)
                    self._c_chunk_tokens.inc(adv - req.prefilled)
                    req.prefilled = adv
                if req.prefilled >= p:
                    pf_done.append(req.slot)
            toks, valid, hist, hlen, rem, cursor = \
                self.engine.mixed_spec_block_async(
                    self._hist_dev, self._hist_len_dev, cursor, plen,
                    active, temps, stops, budgets, specm, sub, k)
            self._hist_dev, self._hist_len_dev = hist, hlen
            self._spec_rem, self._cursor_dev = rem, cursor
            self._enqueue_block("mixed_spec", hlen, (toks, valid), k,
                                snapshot, pf_done, None)
            return True
        # plain mixed: the packed step carries P chunks of C tokens
        # only while a prompt is actually in flight — with no slot in
        # prefill phase it is the decode step (and its RNG stream)
        C = self._mixed_chunk
        P = self._mixed_max_pf if self._prefilling_ahead() else 0
        ahead = np.zeros((S,), np.int64)
        for ent in self._inflight:
            # per-slot emission estimates, each counted for the request
            # it was made for: a block dispatched before a finish that
            # was taken at a lazy drain names the slot's OLD request
            # (0 after a budget finish, positive after a stop-death),
            # and the slot's next request starts with its own budget
            est = ent[7]
            if ent[4] is not snapshot:     # the batch changed since
                stale = [slot for slot, (req, gen) in ent[4].items()
                         if req.done or req.slot != slot
                         or req.preemptions != gen]
                if stale:
                    est = est.copy()
                    est[stale] = 0
            ahead = ahead + est
        budgets = np.maximum(base - ahead, 0).astype(np.int32)
        if not (active & (budgets > 0)).any():
            return False  # every lane is out of budget on device
        # lockstep host sim per lane: cursor end-state, emission count,
        # completion membership. Mirrors the device scan exactly up to
        # stop-deaths, which only shrink emissions after the fact.
        emit_vec = np.zeros((S,), np.int32)
        pf_done = []
        for slot, (req, _gen) in snapshot.items():
            b = int(budgets[slot])
            if not active[slot] or b <= 0:
                continue
            c, p, e = req.prefilled, int(plen[slot]), 0
            for _ in range(k):
                if c < p:
                    c = min(p, c + C)
                    if c < p:
                        continue
                e += 1  # completion first token, or a decode step
                if e >= b:
                    break
            if c != req.prefilled:
                self._c_chunk_tokens.inc(c - req.prefilled)
                req.prefilled = c
            emit_vec[slot] = e
            if req.state == "prefilling" and c >= p:
                pf_done.append(slot)
        cur = self._next_dev if self._next_dev is not None \
            else self.engine.carry(self._next_tokens)
        if self._pbuf_dev is None:
            self._pbuf_dev = jnp.zeros((S, self.engine.cache.max_seq),
                                       jnp.int32)
        block, valid, final, cursor = self.engine.mixed_block_async(
            cur, cursor, self._pbuf_dev, plen, active, temps, stops,
            budgets, sub, k, C, P)
        self._c_chunk_offered.inc(k * P * C)
        self._next_dev, self._cursor_dev = final, cursor
        # a model of experts: the block's routing load rides the same fetch
        load = self.engine.last_expert_load
        outs = (block, valid) if load is None else (block, valid, load)
        self._enqueue_block("mixed", final, outs, k, snapshot,
                            pf_done, emit_vec)
        return True

    def _drain_inflight(self, cause: str = "finish") -> int:
        """FULL drain barrier: fetch every pending first token and
        in-flight block, and read every flush count still pending.
        Returns how many requests finished. In spec mode the device
        budget carry resets to None — the host again knows every
        emitted token, so the next dispatch reseeds it from exact host
        state.

        `cause` labels the barrier in drain_barriers_total{cause=}
        (the membership-change class that forced it: finish,
        page_pressure, cancel, spec, idle, expired, sp_prefill, flush)
        and rides
        the tick's timeline record + the flight-recorder ring."""
        with self._span("drain_barrier"):
            if self._inflight or self._pending_first:
                self._c_barriers.labels(cause).inc()
                self._tick_causes.append(cause)
                if self.flightrec is not None:
                    self.flightrec.note("barrier", cause=cause,
                                        inflight=len(self._inflight))
            blocks, self._inflight = self._inflight, []
            self._spec_rem = None
            finished = self._drain_blocks(blocks, cause)
            # it waits for everything because it drains everything:
            # the last flush runs behind the emission above. A wait on
            # the device (fetch_s counts it) for the value of a
            # counter, under a span of its own: the blocks are fetched
            # already, so the device idles through it
            if self._flush_counts:
                with self._span("drain.flush_count"):
                    t_fetch = time.monotonic()
                    self._count_flushed(wait=True)
                    self._tick_fetch += time.monotonic() - t_fetch
            return finished

    def _drain_oldest(self) -> int:
        """Lazy-drain step: fetch the pending firsts and ONLY the
        oldest in-flight block, leaving newer blocks running on the
        device (the dispatch-ahead overlap — the device computes block
        t+1 while the host emits block t). The fetch reads arrays that
        block t produced itself and launches nothing, so it returns
        when block t ends, whatever was launched after it. Returns
        how many requests finished (the caller takes them there
        without speculation and the seq-parallel lane, and escalates
        to a full barrier with either)."""
        with self._span("drain_oldest"):
            finished = self._drain_blocks([self._inflight.pop(0)]
                                          if self._inflight else [])
            self._count_flushed(wait=False)
            return finished

    def _count_flushed(self, wait: bool) -> None:
        """Add pending flush counts to kv_window_tokens_flushed_total,
        oldest first: those already on hand, or with `wait` (a full
        barrier) all of them. Reading a count launches nothing."""
        pend = self._flush_counts
        while pend and (wait or _device_ready(pend[0])):
            self._c_kv_flushed.inc(int(pend.pop(0)))

    def _drain_blocks(self, blocks: List[tuple],
                      cause: Optional[str] = None) -> int:
        """Fetch + emit the given blocks and do their host bookkeeping
        in chronological order (`cause`: the full barrier's, None for
        a lazy drain); returns how many requests finished. Pending
        first tokens always ride along:
        the seq-parallel lane queues them behind its own barrier, when
        nothing is in
        flight, so they predate every dispatched block; each block's
        [k, S] rows are then emitted in step order per live slot,
        truncated per request at its stop token / max_new by _emit.

        Requests that finished, were cancelled, or were preempted
        between dispatch and drain have their tokens discarded — the
        generation check catches even a preemption readmitted into the
        SAME slot. Slots that went dead mid-block carry frozen repeats
        of their last token, which the done-break below skips (the
        device stopped their writes and length growth inside the scan).
        """
        # Flush the write-combined KV window FIRST (kv_write_combine):
        # the flush dispatch lands after every staged block in device
        # order, so by the time an emission below finishes a request —
        # registering its pages for prefix reuse and releasing them for
        # reclaim — every staged K/V byte is in the pool. Its place in
        # that order is what makes this safe, not its completion: the
        # flushed-token count is a device scalar that _count_flushed
        # reads once it is ready, never this drain's fetch. No-op
        # (None) when nothing is staged.
        t_flush = time.monotonic()
        with self._span("flush"):
            flushed = self.engine.flush_kv_window()
        if flushed is not None:
            dt = time.monotonic() - t_flush
            self._h_kv_flush.observe(dt)
            self._kv_flushes.append(dt)
            self._flush_counts.append(flushed)
            if self.flightrec is not None:
                self.flightrec.note("flush", dispatch_s=dt)
        firsts, self._pending_first = self._pending_first, []
        self._pending_first_keys.clear()  # refreshed: all entries drain
        if not blocks and not firsts:
            return 0
        finished_before = self._c_finished.value
        # the ONE device fetch: the only tick section that blocks on
        # the device — timed for the tick_host_frac / tick_device_frac
        # split (everything else in a tick is host). It reads the
        # drained blocks' own output arrays as they are and launches no
        # program: anything launched here would queue behind the newest
        # block in flight, and the fetch would wait for that block too.
        # Shapes, the validity masks' dtype and the order are the
        # host's to handle.
        with self._span("drain.fetch", blocks=len(blocks)):
            t_fetch = time.monotonic()
            first_vals, block_vals = jax.device_get(
                ([f[3] for f in firsts], [ent[2] for ent in blocks]))
            fetch_s = time.monotonic() - t_fetch
            self._tick_fetch += fetch_s
        newest_ready = None
        if self._inflight:
            # a lazy drain with a newer block in flight: if its carry
            # is not ready the device has work queued while the host
            # goes on (overlapped); if it is, the fetch outlasted it
            # and the device waits from here
            newest_ready = _device_ready(self._inflight[-1][1])
            self._tick_overlapped = not newest_ready
            self._c_overlap.labels("exposed" if newest_ready
                                   else "overlapped").inc()
            if newest_ready:
                self._starve("exposed")
        else:
            # everything in flight has been fetched (a full barrier, or
            # a lazy drain of the only block): the device waits from here
            self._starve(cause or "exposed")
        if blocks:
            self._note_fetch(fetch_s, newest_ready)
        tokens0 = self._c_tokens.value
        with self._span("drain.emit") as ann:
            self._emit_drained(firsts, first_vals, blocks, block_vals)
            ann.set_metadata(tokens=int(self._c_tokens.value - tokens0))
        self._epoch += 1  # outputs / pending-first changed
        return int(self._c_finished.value - finished_before)

    def _note_fetch(self, fetch_s: float,
                    newest_ready: Optional[bool]) -> None:
        """Keep the last 64 block fetches, and call this one a stall
        (_note_stall, with the account as the fetch returned) where it
        took more than STALL_FACTOR times their median and more than
        STALL_MIN_S: one fetch in a few thousand takes 0.3-8 s on the
        chip (ROADMAP.md A7). `newest_ready`: whether the newest block
        in flight had ended when the fetch returned (None: none was)."""
        past = self._fetches
        if fetch_s > STALL_MIN_S and past and self._tick_stall is None:
            usual = statistics.median(past)
            if fetch_s > STALL_FACTOR * usual:
                self._note_stall(fetch_s - usual, self._span_stack[-1],
                                 "drain.fetch",
                                 self._account(time.thread_time())[0],
                                 newest_ready, fetch_s)
        past.append(fetch_s)

    def _emit_drained(self, firsts: List[tuple], first_vals: List,
                      blocks: List[tuple], block_vals: List) -> None:
        """Hand one fetch's host arrays to their requests, in
        chronological order: pending firsts, then each block's rows."""
        now = time.monotonic()
        for (req, gen, slot, _), tok in zip(firsts, first_vals):
            # stale if the request was cancelled or preempted (a
            # readmission queues a fresh entry with a new generation)
            if req.done or req.slot != slot or req.preemptions != gen:
                continue
            self._next_tokens[slot] = int(tok)
            self._emit(req, int(tok))
        for ent, vals in zip(blocks, block_vals):
            kind, _, _, _, snapshot, t_dispatch = ent[:6]
            self._h_decode_block.observe(now - t_dispatch)
            # prefill lanes that completed inside this block leave
            # the prefill group BEFORE their first token (riding
            # the block's emission arrays) is emitted below
            self._mixed_transitions(ent[6], snapshot)
            if kind == "mixed_spec":
                toks3, valid3 = vals  # [rounds, S, C] each
                with self._span("spec_emit"):
                    self._emit_spec(toks3, valid3, snapshot)
                continue
            # [k, S] tokens and their validity mask: a
            # lane emits at most one token per step, valid only on
            # decode steps and the completion step's first token
            rows, ok, *load = vals
            if load and self._experts_held:
                # [.., local, routed] summed over the block's steps
                sh = self._tick_share = self._tick_share or [0.0, 0.0]
                sh[0] += float(load[0][-2])
                sh[1] += float(load[0][-1])
                self._c_expert_rows_local.inc(float(load[0][-2]))
                load = [load[0][:-2]]
            if load and self._has_streams:
                # [.., positions mixed] summed over the block's steps
                hc = self._tick_hc = self._tick_hc or [0.0, 0]
                hc[0] += float(load[0][-1])
                hc[1] += len(rows)
                self._c_hc_rows.inc(float(load[0][-1]))
                load = [load[0][:-1]]
            if load and self._ring_pages:
                # [.., rows read, rows whole] summed over the block's steps
                swa = self._tick_swa = self._tick_swa or [0.0, 0.0]
                swa[0] += float(load[0][3])
                swa[1] += float(load[0][4])
                self._c_swa_read.inc(float(load[0][3]))
                self._c_swa_whole.inc(float(load[0][4]))
                load = [load[0][:3]]
            if load and self._has_ssm:
                # [.., rows, resets] summed over the block's steps
                ssm = self._tick_ssm = self._tick_ssm or [0.0, 0.0, 0]
                ssm[0] += float(load[0][3])
                ssm[1] += float(load[0][4])
                ssm[2] += len(rows)
                load = [load[0][:3]]
            if load and self._is_latent and not self._has_indexer:
                # [.., rows read] summed over the block's steps
                lat = self._tick_latent = self._tick_latent or [0.0, 0]
                lat[0] += float(load[0][3])
                lat[1] += len(rows)
                self._g_latent_rows.set(float(load[0][3]) / len(rows))
                load = [load[0][:3]]
            if load and load[0][2] > 0:    # some step had a real row
                self._tick_expert_loads.append(load[0])
                self._g_experts_touched.set(float(load[0][0]))
                self._g_expert_rows_max.set(float(load[0][1]))
                if len(load[0]) > 3:       # a model with an indexer
                    self._g_kv_rows_live.set(float(load[0][3]))
                    self._g_kv_rows_selected.set(float(load[0][4]))
                    self._g_kv_rows_moved.set(float(load[0][5]))
            for slot, (req, gen) in snapshot.items():
                if req.done or req.slot != slot or req.preemptions != gen:
                    continue
                # ONE vectorized column slice + bulk int conversion per
                # live slot instead of k per-element int(row[slot])
                # casts over the whole [k, S] block (O(k*S) Python work
                # per drain at S=32, k=16)
                toks = rows[:, slot][ok[:, slot]]
                for tok in toks.tolist():
                    self._next_tokens[slot] = tok
                    self._emit(req, tok)
                    if req.done:
                        break

    def _mixed_transitions(self, pf_slots, snapshot: Dict) -> None:
        """Drain-time completion transitions for a mixed block's
        prefill lanes: members whose prompt finished inside the block
        (the dispatch-time host simulation recorded the set) leave the
        prefill group and start decoding. Pages publish for prefix
        reuse after a point where every staged K/V byte is flushed
        (this drain flushed the window first). The generation check
        skips members cancelled or preempted since dispatch."""
        for slot in pf_slots:
            entry = snapshot.get(slot)
            if entry is None:
                continue
            req, gen = entry
            if req.done or req.slot != slot or req.preemptions != gen:
                continue
            if req.state != "prefilling":
                continue  # an earlier drained block already transitioned
            self.alloc.register(slot, req.all_tokens)
            self._prefill_group.remove(req)
            req.state = "running"
            self.running.append(req)
            ran = len(req.all_tokens) - req.cached_at_admit
            self._h_prefill_tokens.observe(ran)
            if self.trace is not None:
                self.trace.event(req.id, "prefill_done", tokens=ran,
                                 total=len(req.all_tokens))
            self._epoch += 1

    def _emit_spec(self, toks3: np.ndarray, valid3: np.ndarray,
                   snapshot: Dict) -> None:
        """Emit one drained spec block: toks3/valid3 [R, S, C] hold
        each round's emissions per slot (valid marks the real ones —
        device-truncated at stop/budget). Host emission walks rounds in
        dispatch order per live slot, re-truncating via _emit's done
        check as a backstop; per-round acceptance feeds the spec
        instruments (a round's emissions are 1 correction/bonus plus
        `count-1` accepted drafts)."""
        R = toks3.shape[0]
        # per-round acceptance ceiling: gamma accepted drafts
        denom = self.engine.spec_emit_width - 1
        # verify forwards that did work: rounds with ANY valid emission
        # (trailing all-dead rounds in a block ran but verified nothing)
        self._c_spec_fwd.inc(int(np.any(valid3, axis=(1, 2)).sum()))
        for slot, (req, gen) in snapshot.items():
            if req.done or req.slot != slot or req.preemptions != gen:
                continue
            t_rows = toks3[:, slot, :].tolist()
            v_rows = valid3[:, slot, :].tolist()
            for r in range(R):
                # a round that emits the request's very
                # first token is the prefill-completion round, not a
                # verify round — it must not count as a zero-acceptance
                # observation
                first_round = req.t_first_token is None
                cnt = 0
                for tok, ok in zip(t_rows[r], v_rows[r]):
                    if not ok:
                        continue
                    cnt += 1
                    self._next_tokens[slot] = tok
                    self._emit(req, tok)
                    if req.done:
                        break
                if cnt and not first_round:
                    self._c_spec_tok.inc(cnt)
                    self._c_spec_acc.inc(max(0, cnt - 1))
                    if req.speculative and denom > 0:
                        self._h_accept.observe((cnt - 1) / denom)
                if req.done:
                    break

    def _emit(self, req: Request, token: int) -> None:
        """Record one generated token; finish/stop bookkeeping."""
        now = time.monotonic()
        if req.t_first_token is None:
            req.t_first_token = now
            self._ttfts.append(req.ttft)
            self._h_ttft.observe(req.ttft)
            if self.slo_ttft_s is not None:
                if req.ttft <= self.slo_ttft_s:
                    self._c_slo_ttft_ok.inc()
                else:
                    self._c_slo_viol.labels("ttft").inc()
            if self.trace is not None:
                self.trace.event(req.id, "first_token", ttft_s=req.ttft)
        else:
            self._itls.append(now - req.t_last_token)
        req.t_last_token = now
        req.output.append(token)
        self._c_tokens.inc()
        if req.on_token is not None:
            req.on_token(req, token)
        hit_stop = req.stop_token >= 0 and token == req.stop_token
        if hit_stop or len(req.output) >= req.max_new_tokens:
            self._finish(req)

    def _finish(self, req: Request, state: str = "finished") -> None:
        self._epoch += 1  # batch membership changes below
        mean_gap = None
        if state == "finished" and len(req.output) > 1 and \
                req.t_first_token is not None:
            mean_gap = ((req.t_last_token - req.t_first_token)
                        / (len(req.output) - 1))
            self._itl_means.append(mean_gap)
            self._h_itl_mean.observe(mean_gap)
        slo_ok = None
        if state == "finished" and (self.slo_ttft_s is not None
                                    or self.slo_itl_s is not None):
            # per-request attainment: a request violates when ANY
            # declared objective is missed (an undelivered first token
            # counts against TTFT — the client never saw one in time)
            viol = False
            if self.slo_ttft_s is not None:
                viol |= req.ttft is None or req.ttft > self.slo_ttft_s
            if self.slo_itl_s is not None and mean_gap is not None:
                if mean_gap <= self.slo_itl_s:
                    self._c_slo_itl_ok.inc()
                else:
                    self._c_slo_viol.labels("itl").inc()
                    viol = True
            slo_ok = not viol
            self._slo_window.append(0.0 if slo_ok else 1.0)
            self._g_slo_burn.set(sum(self._slo_window)
                                 / len(self._slo_window))
        if req.slot is not None:
            # publish the written tokens' full pages before releasing
            # (the latest sampled token's K/V is never written — it
            # would have landed on the NEXT decode step)
            self.alloc.register(req.slot, req.all_tokens[:self._written(req)])
        req.state = state
        req.t_finish = time.monotonic()
        if req in self._prefill_group:  # cancelled mid-chunked-prefill
            self._prefill_group.remove(req)
        if req in self._sp_group:  # cancelled mid-seq-parallel-prefill
            self._sp_group.remove(req)
        if req.slot is not None:
            self.alloc.release(req.slot)
            self.engine.reset_slot(req.slot)
            # mixed carries: plen 0 marks the freed slot decode-phase
            # (a stale cursor then compares >= 0 and never re-enters
            # prefill); readmission reseeds both
            self._plen_host[req.slot] = 0
            self.slots[req.slot] = None
            req.slot = None
        if req in self.running:
            self.running.remove(req)
        if state == "finished":
            self._c_finished.inc()
        if self.trace is not None:
            attrs = {}
            if slo_ok is not None:
                attrs["slo_ok"] = slo_ok
            if mean_gap is not None:
                attrs["itl_mean_s"] = mean_gap
            self.trace.event(req.id, "finish", state=state,
                             tokens=len(req.output),
                             preemptions=req.preemptions,
                             ttft_s=req.ttft, **attrs)
        if req.on_finish is not None:
            req.on_finish(req)

    def _ensure_or_preempt(self, req: Request, need_len: int) -> None:
        """Grow req's pages; under pressure with work in flight, fall
        back to a FULL drain barrier (finishes surfaced there may free
        enough pages — and a victim's pages must never be reclaimed
        while a dispatched block still writes them); only then preempt
        the youngest live request (possibly req itself) until it fits —
        older requests always win page pressure. The victim pool
        includes partially-prefilled members: a young mid-prefill
        admission is the cheapest eviction (no generated tokens to
        recompute) and must not be able to starve an older decoding
        request of pages."""
        while True:
            if req.done or req.slot is None:
                return  # a drain barrier below finished/preempted req
            fresh = self.alloc.grow(req.slot, need_len)
            if fresh is not None:
                if fresh:  # push the grown block table to the device
                    self.engine.set_table_row(req.slot,
                                              self.alloc.pages_of(req.slot))
                return
            if self._inflight or self._pending_first:
                self._drain_inflight("page_pressure")
                continue
            # batch-class requests are preferred victims (shed-first
            # priority semantics); within a class the youngest loses —
            # so an old batch job still yields to a young interactive
            # one, but interactive never pays for batch's pages
            victim = max(self.running + self._prefill_group
                         + self._sp_group,
                         key=lambda r: (r.priority == "batch", r.t_arrive))
            self._preempt(victim)
            if victim is req:
                return

    def _written(self, req: Request) -> int:
        """Tokens whose K/V the device has actually written for req's
        slot: everything prefilled, plus decoded tokens except the last
        sampled one (written on the next step, which never ran).

        A running request whose device-sampled FIRST token has not yet
        drained (output still empty, entry in _pending_first) has every
        one of its all_tokens (= the whole prompt) written by prefill —
        the undrained first token is not in all_tokens, so there is no
        trailing unwritten sample to subtract (r5 review: the old
        blanket -1 under-registered a full page at page boundaries)."""
        if req.state == "prefilling":
            return req.prefilled
        if not req.output and \
                (req.id, req.preemptions) in self._pending_first_keys:
            return len(req.all_tokens)
        return len(req.all_tokens) - 1

    def _preempt(self, req: Request) -> None:
        """Recompute-style preemption: free pages, requeue at the front.
        With prefix caching the pages stay warm in the registry, so
        readmission's "recompute" is usually a cache hit. The victim may
        be a partially-prefilled member (state "prefilling"): its
        prefilled-so-far pages register for reuse like any other and it
        restarts its prompt on readmission."""
        self._epoch += 1  # batch membership changes below
        self._c_preempt.inc()
        if self.flightrec is not None:
            self.flightrec.note("preempt", id=req.id, slot=req.slot,
                                priority=req.priority,
                                generated=len(req.output))
        if self.trace is not None:
            self.trace.event(req.id, "preempt", slot=req.slot,
                             state=req.state,
                             preemptions=req.preemptions + 1,
                             prefilled=req.prefilled,
                             generated=len(req.output))
        # register BEFORE bumping the generation: _written's pending-
        # first-token check matches entries queued under the current one
        self.alloc.register(req.slot, req.all_tokens[:self._written(req)])
        req.preemptions += 1
        self.alloc.release(req.slot)
        self.engine.reset_slot(req.slot)
        self._plen_host[req.slot] = 0  # mixed carries: decode-phase
        self.slots[req.slot] = None
        req.slot = None
        if req in self.running:
            self.running.remove(req)
        elif req in self._prefill_group:
            self._prefill_group.remove(req)
        else:
            self._sp_group.remove(req)
        # all_tokens (prompt + output) are recomputed on readmission
        req.state = "waiting"
        req.prefilled = 0
        req.t_enqueued = time.monotonic()
        self.waiting.appendleft(req)
