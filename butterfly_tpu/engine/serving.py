"""Slot-based serving engine over the paged KV cache.

The continuous-batching scheduler (sched/scheduler.py) drives ONE kind
of jitted device program a tick, static-shape so batch composition
changes never recompile (SURVEY.md §7 "hard parts"):

* `mixed_block` (ISSUE 18; packed since ISSUE 29): k chained steps
  inside ONE jitted `lax.scan` (`_packed_scan`) — one host dispatch and
  one stacked fetch per scheduler tick instead of k. Each step carries
  BOTH phases: a decode-phase slot advances one token (one row) while
  up to P slots in prefill phase chew a C-token chunk of their prompt,
  the S + P*C rows packed into one forward, with the first token
  sampled on device at the step a slot's prefill completes. Phase is a
  pure function of the per-slot chunk cursor riding the carry
  (`cursor < plen`), so admission is a host-side cursor/buffer edit
  between dispatches, never a drain barrier or a dispatch of its own.
  Inactive slots are masked (their lengths don't advance, their writes
  land on the null page). Sampling is vectorized with per-slot
  temperature so requests with different sampling settings batch
  together; per-step RNG keys are derived on device (`fold_in`), and
  per-slot stop ids + remaining-token budgets ride the carry so a slot
  that finishes mid-block goes dead on device (no further writes, no
  length growth, frozen tokens). The returned final-token carry is the
  dispatch-ahead contract: the scheduler chains block t+1 on it BEFORE
  draining block t (up to RuntimeConfig.inflight_blocks undrained), so
  the device runs blocks back-to-back while the host schedules; a dead
  slot's carry stays frozen at its stop id, which starts it dead in
  every later block. With no prompt in flight the scheduler asks for
  P == 0: the same body without a chunk, named `bf_decode_block[_win]`.
* `mixed_spec_block` (speculative_gamma > 0): the lane-wide twin whose
  decode lanes run draft/verify/accept rounds (`_mixed_spec_scan[_win]`
  over cache/paged.py paged_forward[_window]).

Beside them: `sp_prefill_chunk`, the long-prompt seq-parallel lane's
chunk program, and the write-combined window's flush.

Parity contract: tests/test_sched.py and tests/test_serving_mesh.py check
token-for-token equality with InferenceEngine.generate on the contiguous
cache (single-device and meshed respectively); tests/test_mixed_dispatch.py
holds the packed block's tokens to that engine's and to each request
served alone.
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from butterfly_tpu.cache.paged import (
    KVWindow, PagedKVCache, flush_paged_window, init_kv_window,
    by_kind_unsupported, init_paged_cache, paged_forward,
    paged_forward_packed, ring_pages, staged_most,
    paged_forward_window)
from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
from butterfly_tpu.core.mesh import mesh_ctx
from butterfly_tpu.ops import kernel_mode, kernels_default, record_kernels
from butterfly_tpu.engine.sampling import _filter_logits, speculative_accept
from butterfly_tpu.cache.ssm_state import SSMState, init_ssm_state
from butterfly_tpu.models.common import (
    Model, gate_unsupported, indexer_unsupported, latent_unsupported,
    ssm_unsupported, streams_unsupported)


#: the span a program launch runs under (`bf.tick.dispatch.launch` in a
#: trace); its end tells a scheduler that the device has work again
LAUNCH_SPAN = "dispatch.launch"


def _trace_span(name: str, **attrs):
    """`bf.tick.<name>` in the profiler's trace and nothing else: the
    span of an engine that no scheduler drives."""
    return TraceAnnotation("bf.tick." + name, **attrs)


def named(fn, name: str):
    """`fn` (a partial or a closure built here, never a shared
    function) under a stable `__name__`. jax.jit names its program
    after it, so the program reaches a device trace's `XLA Modules`
    as `jit_<name>` (a bare partial is `jit__unknown`) and a trace can
    tell a mixed block from a decode block. Static sizes (k, C, rounds)
    stay out of the name: one name per kind of program."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def bucket_len(n: int, lo: int = 16, hi: Optional[int] = None) -> int:
    """Next power-of-two bucket >= n (floor lo), clamped to hi.

    The clamp keeps an over-long chunk from requesting a prefill
    program wider than the cache supports (positions past the table
    row would silently pad to the null page while the mask/gather view
    stays cache-wide); n > hi is a caller bug and raises."""
    if hi is not None and n > hi:
        raise ValueError(f"{n} tokens exceed the cache's {hi}-token "
                         f"capacity")
    b = lo
    while b < n:
        b *= 2
    if hi is not None and b > hi:
        b = hi
    return b


@jax.named_scope("sample")
def sample_batched(logits: jax.Array, key: jax.Array, temps: jax.Array,
                   top_k: int, top_p: float) -> jax.Array:
    """Per-slot-temperature sampling: temp 0 rows are greedy. [S,V]->[S]."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
    scaled = _filter_logits(logits / safe_t, top_k, top_p)
    drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, drawn, greedy)


def _ngram_drafts(hist, hist_len, gamma: int, ngram: int) -> jax.Array:
    """Prompt-lookup drafts for every slot, ON DEVICE — the batched twin
    of engine._ngram_draft (their match rules must not drift): find the
    most recent STRICTLY-EARLIER occurrence of each slot's trailing
    `ngram` tokens in its history and propose the `gamma` tokens that
    followed it, zero-padded where the continuation runs out or no
    match exists (padding just gets rejected by the verify — no special
    casing). hist [S, H] is the per-slot token history (prompt +
    generated so far), hist_len [S] its live length. O(H * ngram)
    compares per slot — noise next to the verify forward it feeds."""
    S, H = hist.shape
    pos = jnp.arange(H)
    tail_idx = jnp.clip(hist_len[:, None] - ngram + jnp.arange(ngram)[None, :],
                        0, H - 1)
    tail = jnp.take_along_axis(hist, tail_idx, axis=1)          # [S, n]
    win_idx = jnp.clip(pos[:, None] + jnp.arange(ngram)[None, :], 0, H - 1)
    wins = hist[:, win_idx]                                     # [S, H, n]
    ok = (wins == tail[:, None, :]).all(-1)                     # [S, H]
    # window must END before the tail itself starts repeating it
    # (host rule: i ranges over len-ngram-1 .. 0), and a history no
    # longer than the ngram has nothing to look up
    ok &= (pos[None, :] + ngram) <= (hist_len[:, None] - 1)
    ok &= (hist_len > ngram)[:, None]
    i_star = jnp.max(jnp.where(ok, pos[None, :], -1), axis=1)   # [S]
    src = i_star[:, None] + ngram + jnp.arange(gamma)[None, :]  # [S, gamma]
    valid = (i_star >= 0)[:, None] & (src < hist_len[:, None])
    cont = jnp.take_along_axis(hist, jnp.clip(src, 0, H - 1), axis=1)
    return jnp.where(valid, cont, 0).astype(jnp.int32)


class ServingEngine:
    """Device-side half of the serving stack (host half: sched/)."""

    def _refuse_latent(self, mesh) -> None:
        """What a latent-attention model's cached row is not carried
        through refuses the model by name: the row has no heads (nothing
        for a mesh to shard, nothing for int8 KV to scale a head at a
        time), and its pool is one tensor where export, the host tier,
        the lanes and a draft's rollback expect keys and values."""
        rt = self.runtime
        if mesh is not None and mesh.size > 1:
            latent_unsupported(self.cfg, "a device mesh (" + ", ".join(
                f"{a}={n}" for a, n in mesh.shape.items() if n > 1) + ")")
        if rt.kv_quant != "none":
            latent_unsupported(self.cfg, "the int8 KV cache")
        if rt.speculative_gamma > 0:
            latent_unsupported(self.cfg, "speculative decoding")
        if rt.prefix_caching:
            latent_unsupported(self.cfg, "prefix caching (and the host KV "
                                         "tier behind it)")

    def __init__(self, model: Model, params,
                 runtime: Optional[RuntimeConfig] = None, mesh=None,
                 use_kernels: Optional[bool] = None):
        from butterfly_tpu.engine.engine import cast_params
        self.model = model
        self.cfg = model.cfg
        self.runtime = runtime or RuntimeConfig()
        # Optional obs.trace.Tracer (the scheduler shares its own when
        # tracing is on): emits engine-level dispatch events — prefill
        # bucket shapes and block-table syncs — into the global ring.
        # None (the default) keeps every dispatch a single None check.
        self.tracer = None
        self.params = cast_params(params, self.cfg)
        self.mesh = mesh
        self._refuse_latent(mesh)
        stage = mesh.shape.get("stage", 1) if mesh is not None else 1
        if stage > 1 and self.cfg.num_layers % stage != 0:
            raise ValueError(
                f"{self.cfg.num_layers} layers not divisible by "
                f"{stage} pipeline stages")
        # what cannot take the index keys of a sparse-attention indexer,
        # the third kind of cached row, refuses the model by name
        if stage > 1:
            indexer_unsupported(self.cfg, "pipeline serving")
        if mesh is not None and mesh.shape.get("seq", 1) > 1:
            indexer_unsupported(self.cfg, "the sequence-parallel prefill "
                                          "lane")
        if self.runtime.speculative_gamma > 0:
            indexer_unsupported(self.cfg, "speculative decoding")
        if self.runtime.prefix_caching:
            indexer_unsupported(self.cfg, "prefix caching (and the host "
                                          "KV tier behind it)")
        # so does what cannot take a recurrent state a slot (Mamba-2,
        # Gated DeltaNet or Mamba-1 layers): it has no pages to hash,
        # export or roll back, and no sharding of its own yet
        for axis, what in (("stage", "pipeline serving"),
                           ("seq", "the sequence-parallel prefill lane"),
                           ("tensor", "tensor parallelism (the mixer's "
                                      "projections and state)"),
                           ("data", "a data-parallel mesh"),
                           ("expert", "an expert-parallel mesh")):
            if mesh is not None and mesh.shape.get(axis, 1) > 1:
                ssm_unsupported(self.cfg, what)
        if self.runtime.speculative_gamma > 0:
            ssm_unsupported(self.cfg, "speculative decoding (a rejected "
                                      "draft would have to roll a state "
                                      "back)")
        if self.runtime.prefix_caching:
            ssm_unsupported(self.cfg, "prefix caching (and the host KV "
                                      "tier behind it: a state has no "
                                      "pages to key by token hash)")
        # and what adds a sublayer's output to ONE stream in a layer body
        # of its own refuses a model of n residual streams (hc_mult).
        # Speculation is not among them: its verify forward is
        # paged_forward[_window], whose layers call the one pair
        # (models/common.py stream_read / stream_write), and a rejected
        # draft leaves nothing in the streams, which no step keeps
        for axis, what in (("stage", "pipeline serving (a stage hands "
                                     "its successor [rows, D])"),
                           ("seq", "the sequence-parallel prefill lane")):
            if mesh is not None and mesh.shape.get(axis, 1) > 1:
                streams_unsupported(self.cfg, what)
        # and so does a model whose leaves no mesh lays out yet (an
        # attention output gate, norms behind the sublayers, feed-forwards
        # of two shapes outside latent attention)
        if mesh is not None and mesh.size > 1:
            gate_unsupported(self.cfg, "a device mesh (" + ", ".join(
                f"{a}={n}" for a, n in mesh.shape.items() if n > 1) + ")")
        if self.runtime.speculative_gamma > 0 and self.cfg.first_k_dense:
            gate_unsupported(self.cfg, "speculative decoding (its verify "
                                       "is the lane-wide forward)")
        if use_kernels is None:
            # on everywhere but the CPU backend (ops/__init__.py); under
            # a mesh the call sites go through ops/*_sharded (shard_map
            # over data/tensor), so a mesh does not disable them
            use_kernels = kernels_default()
        #: 'off' | 'interpret' | 'compiled' (ops.kernel_mode)
        self.kernel_mode = kernel_mode(use_kernels)
        # kernel call sites traced by this engine's programs, counted
        # while they trace (ops.record_kernels) — /health reports both
        self.kernel_calls: Dict[str, int] = {}
        # Without a mesh the pool and the window are COMMITTED to the
        # device all the same: a block's outputs are, so state that
        # began uncommitted would give a block program two executables,
        # one for its first call and one for the rest.
        cache_shardings = self._home = None if mesh is not None \
            else jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        if mesh is not None:
            # Megatron param layout + paged pool sharded to match (kv
            # heads over `tensor`, slots over `data`): prefill/decode
            # below then compile to one SPMD program over the mesh.
            # Quantized trees route through the quant-aware specs (the
            # float specs would shard a scale's size-1 contraction dim).
            # A tree that arrives in this layout (cli.load_params) is
            # left where it is; the pool is allocated in its layout.
            from butterfly_tpu.parallel.partition import (
                paged_cache_specs, shard_params, to_shardings)
            from butterfly_tpu.quant.int8 import (
                shard_quantized_params, tree_is_quantized)
            if tree_is_quantized(self.params):
                self.params = shard_quantized_params(self.params, self.cfg,
                                                     mesh)
            else:
                self.params = shard_params(self.params, self.cfg, mesh)
            cache_shardings = to_shardings(paged_cache_specs(
                self.cfg, mesh, self.runtime.max_batch_size,
                quant=self.runtime.kv_quant == "int8"), mesh)
        # a model with sliding layers whose streams outlive the window
        # keeps those layers' rows in a ring a slot (cache/paged.py
        # ring_pages; 0: one kind of row, as ever). Prefix reuse shares
        # pages between streams, and a ring is one stream's own
        ring = ring_pages(self.cfg, self.runtime,
                          meshed=mesh is not None and mesh.size > 1)
        if ring and self.runtime.prefix_caching:
            raise NotImplementedError(
                "prefix caching (and the host KV tier behind it) shares "
                "pages between streams; this model's sliding layers "
                f"(window {self.cfg.sliding_window}, max_seq "
                f"{self.runtime.max_seq_len}) keep their rows in a ring of "
                f"{ring} pages a slot, which is one stream's own "
                "(cache/paged.py ring_pages): not supported for this model "
                "at this max_seq")
        self.cache = init_paged_cache(self.cfg, self.runtime,
                                      shardings=cache_shardings, ring=ring)
        # a model with recurrent layers (Mamba-2, Gated DeltaNet,
        # Mamba-1): every slot's recurrent state
        # (cache/ssm_state.py), DONATED to each mixed block and rebound
        # from its result like the window; None for every other model
        self._ssm_state: Optional[SSMState] = init_ssm_state(
            self.cfg, self.runtime.max_batch_size, self._home)
        # Host-side block-table mirror (see set_table_row). Built from
        # the known init value (all rows -> null page) rather than
        # fetching the device array: a multi-process data-sharded table
        # is not addressable from one controller, and doesn't need to be
        # — the host is the only writer.
        self._host_table = np.full(self.cache.page_table.shape,
                                   self.cache.null_page, np.int32)
        self._table_sharding = self.cache.page_table.sharding
        self._table_dirty = False
        # stage>1 routes every paged program through the GPipe schedule
        # (microbatches of slots; pool L dim stage-sharded to match).
        if stage > 1:
            from butterfly_tpu.parallel.pipeline import (
                paged_pipeline_forward, paged_pipeline_packed)
            fwd = partial(paged_pipeline_forward, mesh=mesh)
            self._packed_fwd = partial(paged_pipeline_packed, mesh=mesh)
        else:
            fwd = paged_forward
            self._packed_fwd = paged_forward_packed
        # the speculative pair's verify forward (paged_forward, or its
        # stage-pipelined twin)
        self._fwd = fwd
        self._use_kernels = use_kernels
        # what _launch last called and how many calls it has made: the
        # scheduler's tick record and the launch span carry both
        self.last_program: Optional[str] = None
        self.last_rows = 0
        # the span _put and _launch run under: a context manager
        # `span(name, **attrs)` that writes `bf.tick.<name>` into the
        # profiler's trace. A scheduler that drives this engine puts
        # its own _span here, which also times the tick's phases
        self.span = _trace_span
        # what the newest mixed block's routing asked of the experts, a
        # device f32 [3] (_packed_scan's `load`); None for a dense model.
        # The scheduler fetches it with the block's tokens.
        self.last_expert_load = None
        self.blocks_launched = 0
        # Write-combined KV decode window (RuntimeConfig.kv_write_combine,
        # default on): fused decode/spec blocks stage fresh K/V into an
        # engine-held KVWindow riding the scan carry — the page pool is
        # READ-ONLY inside the block — and the pool takes ONE scatter
        # per flush (scheduler drain) instead of one per token per
        # layer. The window buffer + its per-slot staged count are
        # DONATED to every windowed dispatch and rebound from its
        # results, exactly like the cache (BTF002 contract). The
        # pipeline serving path (stage > 1) threads pools through its
        # stage-local scans, so it keeps per-token writes.
        self._window_mode = bool(self.runtime.kv_write_combine) \
            and stage == 1
        self._kv_window: Optional[KVWindow] = None
        self._win_len = None       # [S] staged count; None = seed zeros
        self._win_dirty = False    # staged entries not yet flushed
        self._win_hwm = 0          # host upper bound on staged entries
        # Mixed blocks: the decode scan with P prefill chunks of C
        # tokens packed beside its S rows, keyed (k, C, P) — static
        # shapes; the scheduler asks for P == 0 whenever no slot is in
        # prefill phase, so the steady-state program is the decode
        # block's shape. Spec-mixed programs key on rounds alone
        # (lane-wide: their C is pinned to gamma + 1, every lane's
        # width real work).
        self._mixed_blocks: Dict[Tuple[int, int, int], object] = {}
        self._mixed_spec_blocks: Dict[int, object] = {}
        self._mixed_spec_win_blocks: Dict[int, object] = {}
        # Seq-parallel chunk-prefill programs (ISSUE 20 move 3), one per
        # bucketed chunk width C — the long-prompt admission lane
        # (sched RuntimeConfig.seq_parallel_threshold) dispatches these.
        self._sp_chunk_progs: Dict[int, object] = {}
        self._flush = jax.jit(flush_paged_window, donate_argnums=(0, 2))

    @contextlib.contextmanager
    def _mesh_ctx(self):
        """Every dispatch runs inside this: the ambient mesh, and the
        kernel record for whatever the dispatch has to trace."""
        with mesh_ctx(self.mesh), record_kernels(self.kernel_calls):
            yield

    def carry(self, value):
        """A host vector [S] as the FIRST binding of a block's carry
        (chain tokens, chunk cursor, staged counts): committed and laid
        out beside cache.lengths, as every later binding, a block's
        output, is — one executable serves the first dispatch and the
        rest."""
        return jax.device_put(np.asarray(value), self.cache.lengths.sharding)

    def _put(self, *operands):
        """The block table and a dispatch's host operands, each a
        (value, dtype or None) pair, to the device under one span."""
        with self.span("dispatch.put"):
            self._sync_table()
            return [jnp.asarray(v, dt) for v, dt in operands]

    def _launch(self, prog, rows: int, *args):
        """Call a jitted program under its launch span (the caller
        holds _mesh_ctx). The span and the tick record name it; `rows`
        is what one step of it puts through the projections (a packed
        mixed step S + P*C, a decode step S, a prefill B*T)."""
        self.blocks_launched += 1
        self.last_program = prog.__name__
        self.last_rows = rows
        with self.span(LAUNCH_SPAN, program=self.last_program,
                       block=self.blocks_launched):
            return prog(*args)

    @property
    def num_slots(self) -> int:
        return self.runtime.max_batch_size

    @property
    def supports_seq_parallel(self) -> bool:
        """Can long prompts route through the chunked seq-parallel
        prefill lane? Needs a live mesh with a seq axis > 1 and no
        pipeline stages (the ring body runs the WHOLE layer stack on
        every seq shard — it has no stage-local slice to ride)."""
        if self.mesh is None:
            return False
        return (self.mesh.shape.get("seq", 1) > 1
                and self.mesh.shape.get("stage", 1) == 1)

    @property
    def sp_degree(self) -> int:
        """Size of the seq mesh axis (1 when meshless)."""
        return self.mesh.shape.get("seq", 1) if self.mesh is not None else 1

    def set_table_row(self, slot: int, pages) -> None:
        """Host allocator -> block table. The device never writes the
        table, so updates accumulate in a host-side numpy mirror and the
        whole (tiny, int32) table transfers ONCE per device call
        (_sync_table) instead of one .at[].set round-trip per admission
        / page-growth (VERDICT r2 weak item 8)."""
        row = np.full((self.cache.page_table.shape[1],),
                      self.cache.null_page, np.int32)
        row[:len(pages)] = pages
        self._host_table[slot] = row
        self._table_dirty = True

    def reset_slot(self, slot: int) -> None:
        self._host_table[slot] = self.cache.null_page
        self._table_dirty = True
        with self._mesh_ctx():
            self.cache = self.cache._replace(
                lengths=self.cache.lengths.at[slot].set(0))

    def _sync_table(self) -> None:
        """Push pending host-side block-table edits to the device."""
        if not self._table_dirty:
            return
        # numpy straight to the sharded layout: one transfer, no
        # default-device staging copy
        tbl = jax.device_put(self._host_table, self._table_sharding)
        self.cache = self.cache._replace(page_table=tbl)
        self._table_dirty = False
        if self.tracer is not None:
            # a table sync is a host->device transfer on the serving
            # loop's critical path — count them in the trace
            self.tracer.event(None, "engine.table_sync")

    # -- write-combined KV window (kv_write_combine) ------------------------

    def _ensure_window(self, need: int) -> None:
        """Make the window able to accept `need` more staged tokens per
        slot: flush when the worst-case staged count would overflow the
        capacity, (re)allocate when the capacity itself is short. Sized
        to inflight_blocks x need so the scheduler's steady-state lazy
        drain flushes once per tick while `inflight_blocks` dispatched
        blocks keep staging."""
        width = self._kv_window.width if self._kv_window is not None else 0
        if self._win_hwm + need > width:
            if self._win_dirty:
                self.flush_kv_window()
            if width < need:
                width = max(1, self.runtime.inflight_blocks) * need
                if self.cache.by_kind and width > staged_most(self.runtime):
                    raise ValueError(
                        f"a window of {width} staged rows a slot: the "
                        "sliding layers' ring was sized for "
                        f"{staged_most(self.runtime)} (cache/paged.py "
                        "ring_pages)")
                shardings = self._home
                if self.mesh is not None:
                    from butterfly_tpu.parallel.partition import (
                        kv_window_specs, to_shardings)
                    shardings = to_shardings(kv_window_specs(
                        self.cfg, self.mesh, self.num_slots,
                        quant=self.cache.quantized), self.mesh)
                self._kv_window = init_kv_window(self.cache, width,
                                                 shardings)
                self._win_len = None
        if self._win_len is None:
            self._win_len = self.carry(np.zeros((self.num_slots,), np.int32))

    def flush_kv_window(self):
        """Flush every staged window entry into the page pool, in
        place, one page of the pool a staged (slot, page) run
        (cache/paged.py flush_paged_window).
        Dispatched like any block — device order puts it after every
        staging dispatch and before anything chained later — so the
        scheduler calls it at its drain points, before page
        registration/reclaim ever reads pool state. Returns the
        device-resident flushed-token count (the scheduler reads it once
        it is ready, never in the fetch of the drain that dispatched
        it), or None if nothing was staged."""
        if not self._win_dirty:
            return None
        with self._mesh_ctx():
            cache, wlen, flushed = self._flush(self.cache, self._kv_window,
                                               self._win_len)
        self.cache, self._win_len = cache, wlen
        self._win_dirty = False
        self._win_hwm = 0
        return flushed

    def drop_kv_window(self) -> None:
        """Discard staged-but-unflushed window state WITHOUT touching
        the device (scheduler.abort_all's wedge path: the device may be
        the thing that is broken). The staged tokens are simply lost —
        their requests are being cancelled host-side anyway — and the
        next windowed dispatch reseeds the staged count from zeros, so
        a later flush can never scatter stale entries into pages that
        have been reclaimed and re-admitted."""
        self._win_dirty = False
        self._win_hwm = 0
        self._win_len = None

    # -- seq-parallel long-prompt prefill (ISSUE 20 move 3) -----------------

    def _sp_chunk_prog(self, C: int):
        """Jitted seq-parallel chunk-prefill program for bucket width C.

        One program per chunk bucket (like _mixed_blocks per k): gather
        the slot's flushed pool prefix for ALL layers, run the chunk
        seq-sharded through sp_chunk_body (ring over the fresh chunk,
        flash-stats merge with the replicated prefix), then scatter the
        chunk's K/V into the page pool with ONE all-layer scatter per
        pool tensor — so the prompt lands paged, prefix-registry-visible
        and evictable, and decode proceeds as an ordinary paged slot.
        """
        prog = self._sp_chunk_progs.get(C)
        if prog is not None:
            return prog
        from jax.sharding import PartitionSpec as P

        from butterfly_tpu.core.mesh import replicated
        from butterfly_tpu.parallel.sequence import sp_chunk_body

        cfg, mesh = self.cfg, self.mesh
        quant = self.cache.quantized
        body = partial(sp_chunk_body, cfg=cfg, quant=quant)

        def run(params, tokens, pools, row, start, clen):
            kp, vp, ksp, vsp = pools
            L, Pp, Kv, pg, H = kp.shape
            mp = row.shape[0]
            S = mp * pg
            # one gather per pool tensor covers every layer's prefix
            if quant:
                pk = kp[:, row].transpose(0, 2, 1, 3, 4) \
                    .reshape(L, 1, Kv, S, H)             # codes [L,1,Kv,S,H]
                pv = vp[:, row].transpose(0, 2, 1, 3, 4) \
                    .reshape(L, 1, Kv, S, H)
                pks = ksp[:, row].reshape(L, mp, Kv, pg) \
                    .transpose(0, 2, 1, 3).reshape(L, 1, Kv, S)
                pvs = vsp[:, row].reshape(L, mp, Kv, pg) \
                    .transpose(0, 2, 1, 3).reshape(L, 1, Kv, S)
                pre_args = (pk, pv, pks, pvs)
                kv_out = (P(None, None, None, "seq", None),
                          P(None, None, None, "seq", None),
                          P(None, None, None, "seq"),
                          P(None, None, None, "seq"))
            else:
                pk = kp[:, row].transpose(0, 1, 3, 2, 4) \
                    .reshape(L, 1, S, Kv, H)             # [L,1,S,Kv,H]
                pv = vp[:, row].transpose(0, 1, 3, 2, 4) \
                    .reshape(L, 1, S, Kv, H)
                pre_args = (pk, pv)
                kv_out = (P(None, None, "seq"), P(None, None, "seq"))
            layers = params["layers"]
            head = {k: v for k, v in params.items() if k != "layers"}
            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), layers),
                          jax.tree.map(lambda _: P(), head),
                          P(None, "seq"), P()) + tuple(
                              P() for _ in pre_args),
                out_specs=(P(None, "seq"), kv_out),
                axis_names={"seq"}, check_vma=False)
            logits, kv = fn(layers, head, tokens, start, *pre_args)
            # flush-style all-layer scatter of the fresh chunk into the
            # pool; pad rows (>= clen) route to the null page
            pos = start + jnp.arange(C)                   # [C] absolute
            valid = jnp.arange(C) < clen
            page_idx = row[jnp.clip(pos // pg, 0, mp - 1)]
            page_idx = jnp.where(valid & (pos < S), page_idx, Pp - 1)
            off = pos % pg
            if quant:
                ck, cv, cks, cvs = kv       # [L,1,Kv,C,H] / [L,1,Kv,C]
                kp = kp.at[:, page_idx, :, off].set(
                    ck[:, 0].transpose(2, 0, 1, 3))       # [C,L,Kv,H]
                vp = vp.at[:, page_idx, :, off].set(
                    cv[:, 0].transpose(2, 0, 1, 3))
                # flat scale dim is kv-major: col = kv*page + offset
                cols = jnp.arange(Kv)[None, :] * pg + off[:, None]
                ksp = ksp.at[:, page_idx[:, None], cols].set(
                    cks[:, 0].transpose(0, 2, 1))         # [L,C,Kv]
                vsp = vsp.at[:, page_idx[:, None], cols].set(
                    cvs[:, 0].transpose(0, 2, 1))
            else:
                ck, cv = kv                 # [L,1,C,Kv,H]
                kp = kp.at[:, page_idx, :, off].set(
                    ck[:, 0].transpose(1, 0, 2, 3).astype(kp.dtype))
                vp = vp.at[:, page_idx, :, off].set(
                    cv[:, 0].transpose(1, 0, 2, 3).astype(vp.dtype))
            last = lax.dynamic_index_in_dim(logits[0], clen - 1, 0,
                                            keepdims=False)
            return last, (kp, vp, ksp, vsp)

        # The pools leave in the layout they came in with (they are
        # donated: the chunk's scatter lands in place, and the next
        # block program sees the shardings it was compiled for); the
        # logits leave replicated.
        pool_sh = tuple(None if p is None else p.sharding for p in (
            self.cache.k_pages, self.cache.v_pages,
            self.cache.k_scale_pages, self.cache.v_scale_pages))
        prog = jax.jit(named(run, "bf_sp_chunk"), donate_argnums=(2,),
                       out_shardings=(replicated(mesh), pool_sh))
        self._sp_chunk_progs[C] = prog
        return prog

    def sp_prefill_chunk(self, slot: int, tokens: list[int],
                         start: int) -> jax.Array:
        """Run one seq-parallel chunk of one LONG prompt; returns the
        chunk's last-token logits [V] (device-resident).

        The scheduler's long-prompt lane (seq_parallel_threshold)
        calls this when the prompt outgrows
        what a block's chunks on one device should chew: the chunk is
        sharded over the seq axis (each shard computes C/N tokens of
        qkv + ring attention), the already-flushed pool prefix is
        attended via the same flash-stats merge, and the chunk's K/V
        lands in the slot's pages — the pool state a block's chunks
        leave, so prefix registry/export/eviction all apply.
        """
        N = self.sp_degree
        C = bucket_len(len(tokens), hi=self.cache.max_seq)
        C = -(-C // N) * N                  # seq axis must divide C
        buf = np.zeros((1, C), np.int32)
        buf[0, :len(tokens)] = tokens
        if self._win_dirty:
            self.flush_kv_window()
        buf, row = self._put((buf, None), (self._host_table[slot], None))
        if self.tracer is not None:
            self.tracer.event(None, "engine.sp_prefill_dispatch",
                              slot=slot, tokens=len(tokens), bucket=C,
                              start=start, degree=N)
        prog = self._sp_chunk_prog(C)
        with self._mesh_ctx():
            pools = (self.cache.k_pages, self.cache.v_pages,
                     self.cache.k_scale_pages, self.cache.v_scale_pages)
            logits, pools = self._launch(
                prog, C, self.params, buf, pools, row,
                jnp.int32(start), jnp.int32(len(tokens)))
            self.cache = self.cache._replace(
                k_pages=pools[0], v_pages=pools[1],
                k_scale_pages=pools[2], v_scale_pages=pools[3],
                lengths=self.cache.lengths.at[slot].set(
                    start + len(tokens)))
        return logits

    @property
    def spec_emit_width(self) -> int:
        """Max tokens a spec round can emit per slot — the C dimension
        of mixed_spec_block_async's (toks, valid) stack and the
        scheduler's budget/reshape unit: gamma drafts + 1 correction."""
        return self.runtime.speculative_gamma + 1

    def _mixed_block_prog(self, k: int, C: int, P: int):
        """The packed mixed block (_packed_scan) of k steps, P chunks
        of C tokens: cursor and cache are donated, and window on the
        window buffer and its staged count too (the pool passes
        through unmodified, aliased); the cursor is the carry the
        scheduler must rebind every dispatch (BTF002 contract). With
        no chunk (P == 0) no slot prefills: the program is a decode
        block in shape and in use and takes the decode block's name,
        so that a trace splits token generation from prompt
        processing."""
        prog = self._mixed_blocks.get((k, C, P))
        if prog is None:
            name = ("bf_mixed_block" if P else "bf_decode_block") \
                + ("_win" if self._window_mode else "")
            prog = jax.jit(
                named(partial(_packed_scan, self.cfg, self._packed_fwd, k, C,
                              P, use_kernel=self._use_kernels), name),
                static_argnums=(12, 13),
                donate_argnums=(2, 3, 4, 5) + ((15,) if self.cfg.has_ssm
                                               else ()))
            self._mixed_blocks[(k, C, P)] = prog
        return prog

    def mixed_block_async(self, tokens, cursor, pbuf, plen,
                          active: np.ndarray, temps: np.ndarray,
                          stops: np.ndarray, budgets, key: jax.Array,
                          k: int, C: int, P: int):
        """Dispatch ONE fused k-step MIXED block, no host sync: decode
        slots advance a token per step while up to P prefill-phase
        slots chew a C-token chunk of their `pbuf` row per step, the
        S + P*C rows of a step packed into one forward (_packed_scan),
        in a single jitted scan covering both phases — admission costs
        no drain barrier, just the host-side cursor/pbuf/table edits
        the scheduler does between dispatches.

        `cursor` [S] is the device-resident chunk-cursor carry
        (DONATED — rebind from the result, exactly like the cache);
        `pbuf` [S, H] the prompt rows (read-only, host-rebound on
        admission); `plen` [S] each slot's prompt length (a slot is in
        prefill phase while cursor < plen). Returns (block [k, S],
        valid [k, S], final [S], cursor): stacked step tokens plus the
        validity mask the drain walks (a prefill step emits only at
        completion), and the chain/cursor carries for the next
        dispatch.

        kv_write_combine: the block stages its K/V into the engine-held
        window (pool read-only inside the scan) and the scheduler's
        next drain flushes it — one pool scatter per drain instead of
        k x L per block; worst case k * C staged entries (a
        prefilling slot advances win_len by its real chunk length), k
        with no chunk. Token outputs are byte-identical either way."""
        tokens, plen, active, temps, stops, budgets = self._put(
            (tokens, None), (plen, jnp.int32), (active, bool),
            (temps, None), (stops, jnp.int32), (budgets, jnp.int32))
        need = k * C if P else k
        if self._window_mode:
            self._ensure_window(need)
        with self._mesh_ctx():
            (block, valid, final, cursor, cache, window, wlen,
             self.last_expert_load, self._ssm_state) = self._launch(
                self._mixed_block_prog(k, C, P), self.num_slots + P * C,
                self.params, tokens, cursor, self.cache,
                self._kv_window, self._win_len,  # None with the window off
                pbuf, plen, active, temps, stops, budgets,
                self.runtime_top_k, self.runtime_top_p, key,
                *(() if self._ssm_state is None else (self._ssm_state,)))
        self.cache, self._kv_window, self._win_len = cache, window, wlen
        if self._window_mode:
            self._win_dirty = True
            self._win_hwm += need
        return block, valid, final, cursor

    def read_pages(self, pids: list[int]) -> Tuple[np.ndarray, np.ndarray,
                                                   Optional[np.ndarray],
                                                   Optional[np.ndarray]]:
        """Fetch page contents to the host for cross-replica KV export
        (fleet/kvtransfer.py): returns (k [L, n, Kv, page, H],
        v [L, n, Kv, page, H], k_scales, v_scales) — scales [L, n,
        Kv*page] iff the pool is int8, else None. Synchronous device
        read; callers hold the serving lock so the scheduler thread
        cannot donate the pools out from under the gather, and only
        REGISTERED pages (content-immutable — a shared full page is
        never rewritten) may be exported, so in-flight decode blocks
        writing other pages cannot race the bytes."""
        by_kind_unsupported(self.cache, "KV page export (read_pages)")
        indexer_unsupported(self.cfg, "KV page export (read_pages)")
        ssm_unsupported(self.cfg, "KV page export (read_pages)")
        latent_unsupported(self.cfg, "KV page export (read_pages)")
        if self._win_dirty:
            self.flush_kv_window()
        idx = jnp.asarray(pids, jnp.int32)
        with self._mesh_ctx():
            k = np.asarray(self.cache.k_pages[:, idx])
            v = np.asarray(self.cache.v_pages[:, idx])
            ks = vs = None
            if self.cache.quantized:
                ks = np.asarray(self.cache.k_scale_pages[:, idx])
                vs = np.asarray(self.cache.v_scale_pages[:, idx])
        return k, v, ks, vs

    def write_pages(self, pids: list[int], k: np.ndarray, v: np.ndarray,
                    k_scales: Optional[np.ndarray] = None,
                    v_scales: Optional[np.ndarray] = None) -> None:
        """Land imported page contents (the read_pages layout) into the
        local pool at freshly claimed page ids (allocator.import_page).
        The pages are not in any slot's table row yet — a later
        admission attaches them read-only via the prefix registry — so
        no in-flight dispatch can be reading them while this scatter
        runs."""
        by_kind_unsupported(self.cache, "KV page import (write_pages)")
        indexer_unsupported(self.cfg, "KV page import (write_pages)")
        ssm_unsupported(self.cfg, "KV page import (write_pages)")
        latent_unsupported(self.cfg, "KV page import (write_pages)")
        idx = jnp.asarray(pids, jnp.int32)
        with self._mesh_ctx():
            kp = self.cache.k_pages.at[:, idx].set(
                jnp.asarray(k, self.cache.k_pages.dtype))
            vp = self.cache.v_pages.at[:, idx].set(
                jnp.asarray(v, self.cache.v_pages.dtype))
            ksp, vsp = self.cache.k_scale_pages, self.cache.v_scale_pages
            if self.cache.quantized:
                ksp = ksp.at[:, idx].set(jnp.asarray(k_scales, jnp.float32))
                vsp = vsp.at[:, idx].set(jnp.asarray(v_scales, jnp.float32))
            self.cache = self.cache._replace(
                k_pages=kp, v_pages=vp,
                k_scale_pages=ksp, v_scale_pages=vsp)

    def _mixed_spec_prog(self, rounds: int):
        prog = self._mixed_spec_blocks.get(rounds)
        if prog is None:
            rt = self.runtime
            prog = jax.jit(
                named(partial(_mixed_spec_scan, self.cfg, self._fwd, rounds,
                              rt.speculative_gamma, rt.speculative_ngram,
                              use_kernel=self._use_kernels),
                      "bf_mixed_spec_block"),
                static_argnums=(10, 11), donate_argnums=(1, 3, 5))
            self._mixed_spec_blocks[rounds] = prog
        return prog

    def _mixed_spec_win_prog(self, rounds: int):
        """Windowed twin of _mixed_spec_prog: donates the history and
        cursor carries plus the cache / window / staged-count triple."""
        prog = self._mixed_spec_win_blocks.get(rounds)
        if prog is None:
            rt = self.runtime
            prog = jax.jit(
                named(partial(_mixed_spec_scan_win, self.cfg, rounds,
                              rt.speculative_gamma, rt.speculative_ngram,
                              use_kernel=self._use_kernels),
                      "bf_mixed_spec_block_win"),
                static_argnums=(12, 13), donate_argnums=(1, 3, 5, 6, 7))
            self._mixed_spec_win_blocks[rounds] = prog
        return prog

    def mixed_spec_block_async(self, hist, hist_len, cursor, plen,
                               active: np.ndarray, temps: np.ndarray,
                               stops: np.ndarray, budgets,
                               spec_mask: np.ndarray, key: jax.Array,
                               rounds: int):
        """Dispatch ONE fused speculative MIXED block — `rounds` chained
        draft → batched-verify → on-device-accept rounds for every
        decode-phase slot, a C-token prompt chunk for every slot in
        prefill phase, in a single jitted lax.scan (_mixed_spec_scan)
        with no host sync. Drafts come from the device-resident token
        history (`hist`/`hist_len`, the carry the scheduler chains
        block t+1 on before block t is drained; prompt lookup,
        _ngram_drafts), acceptance/rollback masks are computed inside
        the scan (rejection-sampling correction at temperature > 0,
        the `_accept_drafts` greedy semantics at 0), and per-slot stop
        ids + remaining budgets kill finished slots on device exactly
        like the plain block. The history carry doubles as the prompt
        buffer (a freshly admitted slot's hist row holds its full
        prompt, hist_len == prompt length); `cursor` is the donated
        chunk-cursor carry and `plen` the per-slot prompt lengths.
        `budgets` may be a host array (first dispatch after a barrier)
        or the previous block's device-resident remainder. Returns
        (toks [rounds, S, C], valid [rounds, S, C], hist, hist_len,
        rem, cursor), all device-resident — the stacked emissions +
        validity masks for the scheduler's stacked drain, and the
        carry for chaining the next dispatch; a completing prefill
        slot's first token arrives as a single valid entry at column 0
        of its completion round.

        kv_write_combine: verify writes stage into the engine-held
        window and only win_len advances by the ACCEPTED count per
        round — rejected drafts' K/V sit past win_len, unattendable,
        and are never flushed into the pool (exact rollback by
        construction).
        """
        hist_len, plen, active, temps, stops, budgets, spec_mask = \
            self._put((hist_len, jnp.int32), (plen, jnp.int32),
                      (active, bool), (temps, None), (stops, jnp.int32),
                      (budgets, jnp.int32), (spec_mask, bool))
        C = self.runtime.speculative_gamma + 1
        if self._window_mode:
            self._ensure_window(rounds * C)
            with self._mesh_ctx():
                (toks, valid, hist, hist_len, rem, cursor, cache,
                 window, wlen) = self._launch(
                        self._mixed_spec_win_prog(rounds),
                        self.num_slots * C,
                        self.params, hist, hist_len, cursor, plen,
                        self.cache, self._kv_window, self._win_len,
                        active, temps, stops, budgets,
                        self.runtime_top_k, self.runtime_top_p, key,
                        spec_mask)
            self.cache, self._kv_window, self._win_len = cache, window, wlen
            self._win_dirty = True
            self._win_hwm += rounds * C
            return toks, valid, hist, hist_len, rem, cursor
        with self._mesh_ctx():
            toks, valid, hist, hist_len, rem, cursor, cache = self._launch(
                self._mixed_spec_prog(rounds), self.num_slots * C,
                self.params, hist, hist_len, cursor, plen, self.cache,
                active, temps, stops, budgets,
                self.runtime_top_k, self.runtime_top_p, key, spec_mask)
        self.cache = cache
        return toks, valid, hist, hist_len, rem, cursor

    # static sampling knobs (per-slot temps are dynamic)
    @property
    def runtime_top_k(self) -> int:
        return self.runtime.top_k

    @property
    def runtime_top_p(self) -> float:
        return self.runtime.top_p


def _packed_scan(cfg: ModelConfig, fwd, k: int, C: int, P: int, params,
                 tokens, cursor, cache: PagedKVCache,
                 window: Optional[KVWindow], win_len, pbuf, plen, active,
                 temps, stops, budgets, top_k: int, top_p: float, key,
                 state: Optional[SSMState] = None,
                 use_kernel: bool = False):
    """k chained PACKED mixed iterations in ONE lax.scan: each step,
    every slot is in exactly one phase. A decode slot advances one
    token; a slot in
    prefill phase chews the next C tokens of its prompt-buffer row.
    A step computes S + P*C rows (`fwd`: cache/paged.py
    paged_forward_packed, or its pipelined twin under stages):
    one row a slot and P chunks, where the lane-wide step this
    replaces gave every slot a C-wide lane and filled S - P of them
    with filler. Phase is a pure function of the carry: a slot is in
    prefill phase while cursor < plen. The scheduler seeds cursor at
    the cached-prefix length on admission, keeps cursor == the slot's
    written-token count, and holds at most P slots in prefill phase
    (_mixed_max_pf); which chunk a slot gets is its rank among them,
    computed here. With P == 0 the step is a decode step, and the
    program is named a decode block.

    A prefill step consumes count = min(C, plen - cursor) real
    positions and advances the slot by that; filler columns write
    nothing. Window on (`window`, `win_len` given; the pool READ-ONLY)
    the advance is win_len's and the entries are staged; window off
    (both None) it is cache.lengths' and they are written to the pool.

    Emissions: a decode step emits its sampled token; a prefill step
    emits ONLY at the step its prefill completes, the slot's first
    token, sampled on device from the chunk's last real column.
    valid[i, s] marks block[i, s] as a real
    emission; the drain walks it like the spec block's validity mask.
    Step i samples with the device-derived key fold_in(key, i), so the
    host pays one dispatch, one operand conversion, and one RNG split
    per BLOCK instead of per token.

    Liveness is the device twin of the host's stop/max_new truncation:
    a slot starts dead if it is inactive, its budget is already spent,
    or (in decode phase) its incoming chain token is its stop id (an
    undrained first token can be EOS); it goes dead the moment an
    emitted token hits the stop id or spends the budget. Dead steps
    freeze the slot's token (the drain discards them anyway), write to
    the null page, and leave lengths at the written-token count — so a
    mid-block finish can never grow pages or attend past the EOS.
    Window on, the pool scatter the scan does not pay per step — and
    the pool COPY the scatter forced, because XLA cannot alias a
    scatter into a scan carry — happens once per scheduler drain
    (engine.flush_kv_window); the window is carried through the layers
    whole as it is through the steps, and written in place
    (cache/paged.py stage_window_layer).

    Returns (block [k, S], valid [k, S], final [S], cursor, cache,
    window, win_len, load): load f32 [3] is what the block's routing
    asked of the experts (models.common.expert_load: distinct experts
    touched, rows of the fullest expert, mean rows an expert), the mean
    over its layers and the steps with a real row; None for a dense
    model. A model with a sparse-attention indexer adds three: the
    positions a live decode row could attend, the positions it
    attended and the rows its read moved out of the cache, the mean
    over the block's layers, rows and steps.

    state (a model with recurrent layers; None for every other): the
    slots' recurrent state (cache/ssm_state.py). It is read and written
    by every step, so it rides the CARRY (the pool stays outside it,
    read-only, as ever) and comes back as the ninth value; `load` then
    ends in two SUMS over the block's steps: the positions pushed
    through a recurrence (decode rows and real chunk columns) and the
    slots that started from zero. A latent-attention model's `load`
    ends in ONE such sum: the cached rows its decode rows read, over
    the layers (cache/paged.py latent_paged_attend); a model of n
    residual streams' in one more, LAST: the positions mixed
    (cache/paged.py _mixed_rows). A latent-attention model WITH an
    indexer gives the indexer's three means and no sum of rows read.
    A cache that keeps the sliding layers' rows apart (cache.by_kind)
    gives two sums: the rows those layers' decode rows read, and what
    they would have read with no window (cache/paged.py _packed_runs).
    Under cfg.experts_held two sums follow everything else: the block's
    expert assignments that fell on a held expert, and all of them.
    """
    S = tokens.shape[0]
    H = pbuf.shape[1]
    ccol = jnp.arange(C)[None, :]
    has_stop = stops >= 0
    is_pf0 = cursor < plen
    # prefill-phase slots skip the chain-token stop check: their
    # incoming token is prompt filler, not an emission
    live = active & (budgets > 0) \
        & jnp.where(has_stop & ~is_pf0, tokens != stops, True)

    windowed = window is not None

    def body(carry, i):
        # kv: the window and its staged counts (the pool, read-only,
        # stays outside the carry), or window off the cache itself
        cur, cursor, kv, live, rem, *st = carry
        pool, win, wlen = (cache, *kv) if windowed else (kv, None, None)
        is_pf = cursor < plen
        # chunk p belongs to the p-th live slot in prefill phase
        cand = is_pf & live
        rank = jnp.cumsum(cand) - 1
        mine = cand[None, :] & (rank[None, :] == jnp.arange(P)[:, None])
        chunk_slot = jnp.argmax(mine, axis=1)                  # [P]
        has_chunk = cand & (rank < P)
        count = jnp.where(has_chunk, jnp.clip(plen - cursor, 0, C), 0)
        chunk_tokens = jnp.take_along_axis(
            pbuf[chunk_slot],
            jnp.clip(cursor[chunk_slot][:, None] + ccol, 0, H - 1), axis=1)
        chunk_count = jnp.where(mine.any(axis=1), count[chunk_slot], 0)
        logits, new, load, *st = fwd(
            params, cfg, cur, pool, chunk_tokens, chunk_slot, chunk_count,
            live & ~is_pf, win, wlen, use_kernel=use_kernel,
            **({"state": st[0]} if st else {}))
        completing = has_chunk & (cursor + count >= plen)
        nxt = sample_batched(logits, jax.random.fold_in(key, i), temps,
                             top_k, top_p)
        emit = live & (completing | ~is_pf)
        nxt = jnp.where(emit, nxt, cur)
        adv = jnp.where(live, jnp.where(is_pf, count, 1), 0)
        kv = (new, wlen + adv) if windowed \
            else new._replace(lengths=pool.lengths + adv)
        cursor = cursor + count
        rem = jnp.where(emit, rem - 1, rem)
        live = live & jnp.where(
            emit, (rem > 0) & jnp.where(has_stop, nxt != stops, True),
            True)
        return (nxt, cursor, kv, live, rem, *st), (nxt, emit, load)

    (final, cursor, kv, _, _, *st), (block, valid, load) = lax.scan(
        body, (tokens, cursor, (window, win_len) if windowed else cache,
               live, budgets, *(() if state is None else (state,))),
        jnp.arange(k, dtype=jnp.int32))
    if windowed:
        window, win_len = kv
    else:
        cache = kv
    if load is not None:
        # the mean over the steps that had a real row (a block's last
        # steps may run on slots that have all finished)
        had = (load[:, 2] > 0).astype(load.dtype)
        experts = (load[:, :3] * had[:, None]).sum(axis=0) \
            / jnp.maximum(had.sum(), 1)
        # a model with an indexer: what a live decode row could attend,
        # what it attended and what its read moved, the mean over the
        # block's rows
        rows = load[:, 3:].sum(axis=0)
        # one chip's share of the experts (cfg.experts_held): the
        # block's assignments that fell on a held expert and all of
        # them, the mean over the layers, SUMS over the steps; they ride
        # last (cache/paged.py before_share)
        if cfg.experts_held:
            rows, share = rows[:-2], rows[-2:]
        if cfg.has_indexer:
            load = jnp.concatenate(
                [experts, rows[1:] / jnp.maximum(rows[0], 1)])
        elif cfg.has_ssm or cfg.is_latent or cfg.hc_mult or cache.by_kind:
            load = jnp.concatenate([experts, rows])
        else:
            load = experts
        if cfg.experts_held:
            load = jnp.concatenate([load, share])
    return (block, valid, final, cursor, cache, window, win_len, load,
            st[0] if st else None)


def _mixed_spec_scan(cfg: ModelConfig, fwd, rounds: int, gamma: int,
                     ngram: int, params, hist, hist_len,
                     cursor, plen, cache: PagedKVCache, active, temps,
                     stops, budgets, top_k: int, top_p: float, key,
                     spec_mask, use_kernel: bool = False):
    """`rounds` chained speculative rounds with prefill lanes in ONE
    lax.scan, emitting 1..gamma+1 tokens per live decode-phase slot
    per round.

    Each round, for every live decode-phase slot at once: (1) draft
    gamma tokens by prompt lookup over the device-resident history
    (_ngram_drafts); (2) run ONE batched (gamma+1)-token verify forward
    over [S, C] chunks (the dense warm multi-token path — the same
    program shape as a chunked warm prefill), writing ALL positions'
    K/V; (3) accept/correct ON DEVICE (sampling.speculative_accept:
    rejection-sampling correction at temperature > 0, `_accept_drafts`
    greedy semantics at 0; accept key fold_in(key, i)); (4) truncate
    the emitted run at the slot's stop id / remaining budget, roll the
    slot's cache length back to its written-token count, and append
    the survivors to the history carry. No host round-trip decides
    acceptance — the host drains stacked (tokens, validity) blocks
    after the fact, exactly like decode.

    Prefill-phase slots (cursor < plen) spend the round's
    [S, C = gamma+1] forward on a C-token chunk of their HISTORY row
    instead — under spec the history carry already holds the full
    prompt at admission (hist_len == prompt length), so it doubles as
    the prompt buffer and no separate chunk operand exists. A
    completing slot samples its first token from the chunk's last real
    column under fold_in(key, 2 * rounds + i) — a key stream that
    cannot collide with the accept keys' 0..rounds-1 index range — and
    emits it as ONE valid entry at column 0 of its completion round;
    the unified history-append then lands it at position hist_len
    exactly like an accepted token, so the next round's ngram lookup
    already sees it.

    KV correctness under rejection is the write-then-attend argument
    (engine.generate_speculative docs): rejected positions hold stale
    K/V past the rolled-back length, and the next round's chunk —
    which starts at that length and spans gamma+1 >= the stale run —
    rewrites them before any query can attend that far. Writes past a
    slot's allocated pages (the last verify's slack) land on the null
    page via the block-table default, same as dead-slot decode writes.

    Liveness is the plain block's contract: a slot starts dead if
    inactive, out of budget, or (in decode phase) its last history
    token is its stop id; it goes dead the round a valid emission hits
    the stop id or spends the budget (lengths freeze, later writes
    null out via `active` masking), so a chained block dispatched
    before this one drains starts it dead too.

    Returns (toks [rounds, S, C], valid [rounds, S, C], hist,
    hist_len, rem, cursor, cache) — valid[r, s, c] marks toks[r, s, c]
    as a real emission of round r (in (round, position) order).
    """
    S, H = hist.shape
    C = gamma + 1
    has_stop = stops >= 0
    col = jnp.arange(C)[None, :]
    rows = jnp.arange(S)[:, None]
    is_pf0 = cursor < plen
    last0 = jnp.take_along_axis(
        hist, jnp.clip(hist_len - 1, 0, H - 1)[:, None], axis=1)[:, 0]
    # prefill-phase slots skip the last-token stop check: their history
    # tail is prompt, not an emission (a prompt MAY end with the stop id)
    live0 = active & (budgets > 0) \
        & jnp.where(has_stop & ~is_pf0, last0 != stops, True)

    def body(carry, i):
        hist, hlen, cursor, cache, live, rem = carry
        is_pf = cursor < plen
        count = jnp.where(is_pf, jnp.clip(plen - cursor, 0, C), 0)
        drafts = _ngram_drafts(hist, hlen, gamma, ngram)
        last = jnp.take_along_axis(
            hist, jnp.clip(hlen - 1, 0, H - 1)[:, None], axis=1)[:, 0]
        pchunk = jnp.take_along_axis(
            hist, jnp.clip(cursor[:, None] + col, 0, H - 1), axis=1)
        toks = jnp.where(
            is_pf[:, None], pchunk,
            jnp.concatenate([last[:, None], drafts], axis=1))
        W = cache.lengths
        logits, cache = fwd(params, cfg, toks, cache, active=live,
                            use_kernel=use_kernel)
        emitted, n_acc = speculative_accept(
            logits, drafts, jax.random.fold_in(key, i), temps,
            top_k, top_p, spec_mask)
        # decode lanes: emitted prefix n_acc+1, clipped at the remaining
        # budget, cut at the first stop id INCLUSIVE (the stop token
        # itself emits, like _emit's host truncation)
        cand = (col <= n_acc[:, None]) & (col < rem[:, None]) \
            & ~is_pf[:, None]
        stop_at = cand & has_stop[:, None] & (emitted == stops[:, None])
        prior = jnp.cumsum(stop_at.astype(jnp.int32), axis=1) \
            - stop_at.astype(jnp.int32)
        valid = cand & (prior == 0) & live[:, None]
        # prefill lanes: completion emits the slot's FIRST token at
        # column 0, sampled from the chunk's last real column
        completing = is_pf & (cursor + count >= plen)
        sidx = jnp.clip(count - 1, 0, C - 1)
        lg = jnp.take_along_axis(logits, sidx[:, None, None],
                                 axis=1)[:, 0, :]
        first = sample_batched(
            lg, jax.random.fold_in(key, 2 * rounds + i), temps, top_k,
            top_p)
        emitted = jnp.where(is_pf[:, None] & (col == 0),
                            first[:, None], emitted)
        valid = valid | ((completing & live)[:, None] & (col == 0))
        m = valid.sum(axis=1).astype(jnp.int32)
        # per-slot advance: a prefill step keeps its real chunk length,
        # a decode round its accepted count — the verify's +C rolls
        # back to exactly the written tokens either way
        adv = jnp.where(is_pf, count, m)
        cache = cache._replace(lengths=jnp.where(live, W + adv, W))
        wpos = jnp.clip(hlen[:, None] + col, 0, H - 1)
        cur = jnp.take_along_axis(hist, wpos, axis=1)
        hist = hist.at[rows, wpos].set(jnp.where(valid, emitted, cur))
        hlen = jnp.where(live, hlen + m, hlen)
        cursor = jnp.where(live & is_pf, cursor + count, cursor)
        rem = jnp.where(live, rem - m, rem)
        died = (valid & has_stop[:, None]
                & (emitted == stops[:, None])).any(axis=1)
        live = live & ~died & (rem > 0)
        return (hist, hlen, cursor, cache, live, rem), (emitted, valid)

    (hist, hist_len, cursor, cache, _, rem), (toks_blk, valid_blk) = \
        lax.scan(body, (hist, hist_len, cursor, cache, live0, budgets),
                 jnp.arange(rounds, dtype=jnp.int32))
    return toks_blk, valid_blk, hist, hist_len, rem, cursor, cache


def _mixed_spec_scan_win(cfg: ModelConfig, rounds: int, gamma: int,
                         ngram: int, params, hist, hist_len,
                         cursor, plen, cache: PagedKVCache,
                         window: KVWindow, win_len, active, temps,
                         stops, budgets, top_k: int, top_p: float, key,
                         spec_mask, use_kernel: bool = False):
    """Write-combined twin of _mixed_spec_scan — lane semantics are
    IDENTICAL (the spec parity grid pins byte-equality); only the K/V
    write target differs. Each round's [S, C] forward stages ALL C
    positions into the window at offset win_len, then win_len advances
    by the per-slot real count only (chunk length for a prefill lane,
    accepted count for a decode lane) — the window-side analogue of
    the cache-length rollback, but stronger: filler, rejected drafts
    and dead-round repeats sit past win_len, no query can ever attend
    them (insert positions start at the flushed base + win_len >=
    every valid query's horizon), and the flush never writes them, so
    the POOL never holds stale speculative state (window-off relies on
    the write-then-attend rewrite argument for those positions). The
    next round's C-wide write at the new win_len overwrites the stale
    run inside the window buffer itself.

    Returns (toks [rounds, S, C], valid [rounds, S, C], hist,
    hist_len, rem, cursor, cache, window, win_len).
    """
    S, H = hist.shape
    C = gamma + 1
    has_stop = stops >= 0
    col = jnp.arange(C)[None, :]
    rows = jnp.arange(S)[:, None]
    is_pf0 = cursor < plen
    last0 = jnp.take_along_axis(
        hist, jnp.clip(hist_len - 1, 0, H - 1)[:, None], axis=1)[:, 0]
    live0 = active & (budgets > 0) \
        & jnp.where(has_stop & ~is_pf0, last0 != stops, True)

    def body(carry, i):
        hist, hlen, cursor, win, wlen, live, rem = carry
        is_pf = cursor < plen
        count = jnp.where(is_pf, jnp.clip(plen - cursor, 0, C), 0)
        drafts = _ngram_drafts(hist, hlen, gamma, ngram)
        last = jnp.take_along_axis(
            hist, jnp.clip(hlen - 1, 0, H - 1)[:, None], axis=1)[:, 0]
        pchunk = jnp.take_along_axis(
            hist, jnp.clip(cursor[:, None] + col, 0, H - 1), axis=1)
        toks = jnp.where(
            is_pf[:, None], pchunk,
            jnp.concatenate([last[:, None], drafts], axis=1))
        logits, win = paged_forward_window(params, cfg, toks, cache,
                                           win, wlen, active=live,
                                           use_kernel=use_kernel)
        emitted, n_acc = speculative_accept(
            logits, drafts, jax.random.fold_in(key, i), temps,
            top_k, top_p, spec_mask)
        cand = (col <= n_acc[:, None]) & (col < rem[:, None]) \
            & ~is_pf[:, None]
        stop_at = cand & has_stop[:, None] & (emitted == stops[:, None])
        prior = jnp.cumsum(stop_at.astype(jnp.int32), axis=1) \
            - stop_at.astype(jnp.int32)
        valid = cand & (prior == 0) & live[:, None]
        completing = is_pf & (cursor + count >= plen)
        sidx = jnp.clip(count - 1, 0, C - 1)
        lg = jnp.take_along_axis(logits, sidx[:, None, None],
                                 axis=1)[:, 0, :]
        first = sample_batched(
            lg, jax.random.fold_in(key, 2 * rounds + i), temps, top_k,
            top_p)
        emitted = jnp.where(is_pf[:, None] & (col == 0),
                            first[:, None], emitted)
        valid = valid | ((completing & live)[:, None] & (col == 0))
        m = valid.sum(axis=1).astype(jnp.int32)
        adv = jnp.where(is_pf, count, m)
        wlen = jnp.where(live, wlen + adv, wlen)
        wpos = jnp.clip(hlen[:, None] + col, 0, H - 1)
        cur = jnp.take_along_axis(hist, wpos, axis=1)
        hist = hist.at[rows, wpos].set(jnp.where(valid, emitted, cur))
        hlen = jnp.where(live, hlen + m, hlen)
        cursor = jnp.where(live & is_pf, cursor + count, cursor)
        rem = jnp.where(live, rem - m, rem)
        died = (valid & has_stop[:, None]
                & (emitted == stops[:, None])).any(axis=1)
        live = live & ~died & (rem > 0)
        return (hist, hlen, cursor, win, wlen, live, rem), \
            (emitted, valid)

    (hist, hist_len, cursor, window, win_len, _, rem), \
        (toks_blk, valid_blk) = lax.scan(
            body, (hist, hist_len, cursor, window, win_len, live0,
                   budgets),
            jnp.arange(rounds, dtype=jnp.int32))
    return (toks_blk, valid_blk, hist, hist_len, rem, cursor, cache,
            window, win_len)
