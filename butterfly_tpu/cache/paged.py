"""Paged KV cache: block-table layout for continuous batching.

Realizes BASELINE.json configs[4] ("continuous batching + paged KV cache");
the reference has no implementation (SURVEY.md §0). Design (vLLM-style
semantics, TPU-native mechanics):

* One global page pool per layer stack: k/v_pages [L, P, Kv, page, H] in
  HBM. Sequences own pages through a block table [slots, max_pages] of
  page ids; page P-1 is reserved as the null page (block tables are
  initialized to it, so gathers from unallocated slots read zeros and the
  causal mask hides them).
* The dim order puts (page, H) minor: TPU tiles pad the two minor dims
  ((16,128) bf16, (32,128) int8), so a Kv-minor layout would inflate
  physical HBM 2-4x for GQA models (Kv=8 pads to the sublane tile); with
  page_size >= the sublane tile there is no padding at all, and each
  (kv, page) read is one contiguous [page, H] tile run.
* int8 mode (RuntimeConfig.kv_quant="int8"): k/v_pages hold int8 codes
  and k/v_scale_pages [L, P, Kv*page] hold one f32 scale per stored
  vector (absmax over head_dim / 127 — models.common.quantize_kv). The
  scale dim is FLATTENED kv-major: (a) the page-granular decode kernel
  streams it as one lane-aligned [Kv*page] row per page (a 2-D [Kv,page]
  block would need a sublane->lane relayout in-kernel), and (b) a
  `tensor`-axis shard of the Kv dim is a contiguous chunk of the flat
  dim (chunk = (Kv/tp)*page), so the same PartitionSpec machinery
  shards codes and scales consistently. Decode streams half the cache
  bytes from HBM; dequantization fuses into the attention dots (K scale
  applied to scores output-side, V scale folded into the probs), so no
  bf16 copy of the pool ever materializes.
* Token writes are scatters (`.at[...].set`) at (page_table[slot, t//page],
  t%page) — XLA Scatter keeps the pool HBM-resident, the paged analogue of
  the contiguous cache's DynamicUpdateSlice.
* Attention reads gather each slot's pages back into a contiguous
  [B, S_max, ...] view per layer (XLA Gather). This reference path reads
  the same bytes a contiguous cache would; the Pallas paged-attention
  kernel (ops/) replaces gather+attend for decode so only *used* pages are
  touched.
* Page allocation/free is host-side (cache/allocator.py) — the device
  never sees dynamic shapes, only a static pool and int32 tables.
* TWO layouts of a page, chosen in one place from the configuration
  (pool_row): HEAD-major [L, P, Kv, page, H], above, for every model the
  paged kernel serves (a head's page is whole tiles, and tensor
  parallelism shards dim 2); TOKEN-major [L, P, 1, page, Kv*H], "one
  head of Kv*H", for a model with a sparse-attention indexer, whose
  decode rows read single selected tokens: XLA's gather pays by the ROW
  (about 10 ns whatever the row holds), and a token whose Kv heads lie
  contiguous is ONE row of Kv*H values where the head-major page makes
  it Kv rows of H (PERF.md PR 37). A page is the same bytes and whole
  tiles either way, and writes, staging, the flush, the table gathers
  and the window are indifferent to which: they see Kv' heads of H'.
  Under tensor parallelism a token-major row shards its minor dim, a
  chip's KV heads contiguous in it (parallel/partition.py).
* A THIRD row, for a latent-attention model (cfg.kv_lora_rank): ONE
  tensor [L, P, 1, page, Rp] and NO value pool (v_pages is None). A
  token's row is its normed latent and its rotated key (cfg.latent_row
  values: 576, 1,152 B, for JoyAI-LLM-Flash) laid in whole lanes, Rp =
  latent_row rounded up to 128 (640), the lanes behind the values zero:
  the chip's tiled layout pads a row of 576 bfloat16 to five lane tiles
  whatever shape is declared, and the kernel that reads a stream's
  pages (ops/latent_attention.py) copies whole tiles, so the declared
  shape is what the memory holds. No heads, nothing to quantize a head
  at a time, nothing to shard by head: int8 KV and every mesh refuse
  it by name. Writes, staging, the flush and the table gathers see one
  head of Rp, as they see a token-major pool.
* A latent-attention model whose rows an indexer SELECTS (GLM-5: DSA
  over MLA) holds that row AND the index-key pool [L, P, 1, page, Hi]
  beside it, under the one table: two leaves in the pool, the window,
  the stage and the flush, where every other model has one, three or
  five.
* An index key too is laid in whole lanes (index_row: Keye's 64 values
  in a row of 128, the lanes behind them zero; GLM-5's 128 as they
  are), in the pool and in the window alike, for the latent row's
  reason: the tiled layout pads a row of 64 bfloat16 to a lane tile
  whatever is declared, and Mosaic copies whole tiles, so only a pool
  declared so can be read where it lies (ops/index_scores.py; PERF.md,
  PR 53). The writers pad a key on its way in (_in_lanes) and XLA's
  reader cuts the view back to Hi.
* TWO LIFETIMES of rows, where a model has sliding layers and streams
  that outlive the window (ring_pages): a sliding layer at position p
  reads rows p - window + 1 .. p and never an older one, so its rows
  live in a pool of their own, k_ring / v_ring [Ls, S*R + 1, Kv, page,
  H], which a slot owns R pages of from admission to release, as a
  RING: ring_table [S, R], position p's row in entry (p // page) % R.
  R = ceil((window + staged rows) / page) + 1 pages hold the window,
  what the write-combined window may stage and a page more, so a page
  is rewritten only when no query of the stream can reach its rows
  again: a sliding layer holds R pages a stream whatever the context.
  k_pages / v_pages then hold the FULL layers alone under the page
  table and the free list, as ever. The write-combined window keeps
  every attention layer (it is small), read by the layer's index among
  them; the pools are read by the index among the layers of the kind
  (models.common.layer_runs by_window: a run of layers is of one kind,
  and which pool it reads is a choice made while tracing). The flush
  writes a staged row to the pool of its layer's kind. Nothing else
  changes, and a model without sliding layers, or one whose table is
  no longer than its ring, allocates and compiles what it did.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
# Module-level, deliberately: these all run INSIDE traced code (every
# decode/prefill/spec dispatch), and a lazy in-function import executes
# on every trace — the same class of hot-path tax PR 3's _apply_top_k
# hoist removed (ISSUE 13 satellite: the remaining paged_layer_body /
# paged_forward in-function imports hoisted alongside the new warm-flash
# call). No cycle: models.common imports core.config, quant.int8, and
# ops.flash_attention, none of which import this module; the ops kernel
# wrappers import nothing project-local at module level.
from butterfly_tpu.models.common import (
    _cast_float, attend, attend_token_rows, attn_gate, attn_output,
    early_router_logits, embed_tokens, experts_in_place, ffn_close,
    final_logits, ffn_run, gate_unsupported, index_proj, index_scores,
    indexer_unsupported,
    latent_attend, latent_proj, latent_queries, latent_unsupported, layer_at,
    layer_experts, layer_mask,
    layer_pattern_of, layer_runs, layer_stack, make_mask, qkv_proj,
    quantize_kv, run_layer_at, select_mask, stream_fold, stream_read,
    stream_write,
    select_topk, ssm_unsupported, RECURRENT_STACKS)
from butterfly_tpu.ops import note_kernel
from butterfly_tpu.ops import index_scores as paged_index
from butterfly_tpu.ops import latent_attention, sparse_attention
from butterfly_tpu.ops import select_mask as paged_select
from butterfly_tpu.ops.flash_attention import flash_attention_sharded
from butterfly_tpu.ops.paged_attention import paged_attention_sharded
from butterfly_tpu.ops.window_stage import stage_window_sharded, window_step
from butterfly_tpu.cache.ssm_state import SSMState, advance_packed


class PagedKVCache(NamedTuple):
    # [L, P, Kv, page, H] (int8 codes when quantized); token-major
    # [L, P, 1, page, Kv*H] for a model with an indexer (pool_row). L
    # counts the layers that OWN rows: a model with recurrent layers holds
    # pages for its attention layers alone (cfg.num_attn_layers, 0 for
    # a model of Mamba layers only: the table, the lengths and the
    # flush still work, over no layer)
    k_pages: jax.Array
    v_pages: Optional[jax.Array]  # None for a latent-attention model:
                           # k_pages holds its ONE row a token (pool_row)
    page_table: jax.Array  # [slots, max_pages] int32, null = P-1
    lengths: jax.Array     # [slots] int32 tokens written per slot
    k_scale_pages: Optional[jax.Array] = None  # [L, P, Kv*page] f32 iff int8
    v_scale_pages: Optional[jax.Array] = None
    # [L, P, 1, page, Hi]: the index keys of a model with a sparse-
    # attention indexer (one a token), a third kind of cached row under
    # the same page table, free list, window and flush
    ki_pages: Optional[jax.Array] = None
    # the SLIDING layers' rows, where they are kept apart (ring_pages;
    # the module's docstring): pools [Ls, S*R + 1, Kv, page, H] (the
    # last page null), the ring [S, R] of the pages a slot owns (fixed:
    # slot s owns s*R .. s*R + R - 1; it is a table so that the kernel
    # and the flush walk it as they walk page_table), and which of the
    # attention layers, as the window counts them, are full and which
    # slide ([Lf], [Ls] int32). k_pages / v_pages then hold the full
    # layers alone. All None for a cache of one kind
    k_ring: Optional[jax.Array] = None
    v_ring: Optional[jax.Array] = None
    ring_table: Optional[jax.Array] = None
    full_layers: Optional[jax.Array] = None
    ring_layers: Optional[jax.Array] = None

    @property
    def by_kind(self) -> bool:
        return self.k_ring is not None

    @property
    def num_layers(self) -> int:
        """Attention layers with rows here, both kinds: the window's
        leading dim."""
        return self.k_pages.shape[0] + (self.k_ring.shape[0]
                                        if self.by_kind else 0)

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[3]

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def null_page(self) -> int:
        return self.k_pages.shape[1] - 1

    @property
    def max_seq(self) -> int:
        return self.page_table.shape[1] * self.page_size

    @property
    def num_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale_pages is not None


#: a TPU tile's minor dim: a latent row is laid in whole lanes
LANES = 128


def pool_row(cfg: ModelConfig) -> Tuple[int, int]:
    """(heads, width) of a cached token in a page [heads, page, width]:
    the ONE place that decides the pool's layout, from the configuration.
    A model with a sparse-attention indexer holds a token as one row
    across its KV heads (token-major: (1, Kv*H)), every other model a
    row a KV head (head-major: (Kv, H)); the module's docstring says
    why. The window and the sharding specs follow the pool."""
    if cfg.is_latent:
        # the latent and the rotary key in whole lanes (the module's
        # docstring): 576 values in a row of 640
        return 1, -(-cfg.latent_row // LANES) * LANES
    if cfg.has_indexer:
        return 1, cfg.num_kv_heads * cfg.head_dim
    return cfg.num_kv_heads, cfg.head_dim


def index_row(cfg: ModelConfig) -> int:
    """Width of a cached index key, in the pool and in the window:
    index_head_dim in whole lanes (the module's docstring)."""
    return -(-cfg.index_head_dim // LANES) * LANES


def _in_lanes(ki: jax.Array, width: int) -> jax.Array:
    """Index keys [.., Hi] as they are cached: zeros behind them up to
    the pool's `width` (index_row)."""
    return jnp.pad(ki, [(0, 0)] * (ki.ndim - 1) + [(0, width - ki.shape[-1])])


def pool_layout(cfg: ModelConfig) -> str:
    """pool_row by name, as /health reports it: "token", "head", or
    "latent" (one row a token and no value pool)."""
    if cfg.is_latent:
        return "latent"
    return "token" if pool_row(cfg)[0] != cfg.num_kv_heads else "head"


def staged_most(runtime: RuntimeConfig) -> int:
    """The most rows a slot's write-combined window ever holds: what
    the engine sizes it to (engine/serving.py _ensure_window), blocks in
    flight x steps a block x the chunk's width."""
    return max(1, runtime.inflight_blocks) * runtime.decode_steps_per_tick \
        * max(1, min(runtime.prefill_chunk, runtime.prefill_inline_budget))


def ring_pages(cfg: ModelConfig, runtime: RuntimeConfig,
               meshed: bool = False) -> int:
    """R, the pages of a sliding layer's ring a slot (the module's
    docstring), or 0 where the cache is of ONE kind, as it always was:
    a model none of whose layers slides; a table no longer than the
    ring would be (max_seq within the window and what is staged:
    nothing to save); and what the ring is not carried through yet: no
    write-combined window (the ring is written by its flush alone), an
    int8 pool (its scale pools), speculation (its verify is the
    lane-wide forward), pipeline stages and every other mesh."""
    if not cfg.slides or cfg.has_indexer or cfg.is_latent:
        return 0
    if not runtime.kv_write_combine or runtime.kv_quant != "none" \
            or runtime.speculative_gamma > 0 or meshed:
        return 0
    page = runtime.page_size
    R = -(-(cfg.sliding_window + staged_most(runtime)) // page) + 1
    return R if R < -(-runtime.max_seq_len // page) else 0


def pool_kinds(cache: PagedKVCache) -> Optional[dict]:
    """What /health reports of a cache by kind, None for a cache of one:
    for "full" (the page table's pool) and "slide" (the rings') the
    layers, the pages (the null page apart), the bytes of the pool's
    keys and values, and for the rings the pages a slot owns."""
    if not cache.by_kind:
        return None

    def kind(k, v):
        return {"layers": k.shape[0], "pages": k.shape[1] - 1,
                "bytes": int(k.nbytes + v.nbytes)}

    return {"full": kind(cache.k_pages, cache.v_pages),
            "slide": {**kind(cache.k_ring, cache.v_ring),
                      "ring_pages": cache.ring_table.shape[1]}}


def init_paged_cache(cfg: ModelConfig, runtime: RuntimeConfig,
                     dtype: Optional[jnp.dtype] = None,
                     shardings: Optional[PagedKVCache] = None,
                     ring: int = 0) -> PagedKVCache:
    """Pool sized from the runtime config (+1 reserved null page).
    ring: ring_pages' R where the sliding layers' rows are kept apart.

    runtime.kv_quant="int8" allocates int8 code pools + f32 scale pools
    (the serving-path twin of models.common.init_cache(quant="int8")).
    `shardings` (a PagedKVCache of NamedShardings, from
    parallel/partition.py paged_cache_specs) allocates every leaf in its
    mesh layout — the whole pool never sits on one device."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    page = runtime.page_size
    max_pages = -(-runtime.max_seq_len // page)
    P = runtime.num_pages or runtime.max_batch_size * max_pages
    P += 1  # null page
    heads, width = pool_row(cfg)
    L = cfg.num_attn_layers
    S = runtime.max_batch_size
    slides = np.asarray(cfg.slides[:L], bool) if ring else None
    if ring:
        L = int((~slides).sum())
    shape = (L, P, heads, page, width)
    if runtime.kv_quant not in ("none", "int8"):
        raise ValueError(f"unknown kv quant {runtime.kv_quant!r}")
    if runtime.kv_quant == "int8":
        indexer_unsupported(cfg, "the int8 KV cache")
        latent_unsupported(cfg, "the int8 KV cache")
    ki_shape = (L, P, 1, page, index_row(cfg))

    def build():
        table = jnp.full((runtime.max_batch_size, max_pages), P - 1,
                         jnp.int32)
        lengths = jnp.zeros((runtime.max_batch_size,), jnp.int32)
        if runtime.kv_quant == "int8":
            sshape = (L, P, cfg.num_kv_heads * page)
            return PagedKVCache(
                k_pages=jnp.zeros(shape, jnp.int8),
                v_pages=jnp.zeros(shape, jnp.int8),
                page_table=table, lengths=lengths,
                k_scale_pages=jnp.zeros(sshape, jnp.float32),
                v_scale_pages=jnp.zeros(sshape, jnp.float32),
            )
        by_kind = {}
        if ring:
            rshape = (int(slides.sum()), S * ring + 1, heads, page, width)
            by_kind = dict(
                k_ring=jnp.zeros(rshape, dtype),
                v_ring=jnp.zeros(rshape, dtype),
                ring_table=jnp.arange(S * ring, dtype=jnp.int32
                                      ).reshape(S, ring),
                full_layers=jnp.asarray(np.flatnonzero(~slides), jnp.int32),
                ring_layers=jnp.asarray(np.flatnonzero(slides), jnp.int32))
        return PagedKVCache(
            k_pages=jnp.zeros(shape, dtype),
            v_pages=None if cfg.is_latent else jnp.zeros(shape, dtype),
            page_table=table, lengths=lengths,
            ki_pages=jnp.zeros(ki_shape, dtype) if cfg.has_indexer else None,
            **by_kind)

    return jax.jit(build, out_shardings=shardings)()


def _write_targets(page_table, num_pages: int, page: int, start, T: int,
                   active=None):
    """(page, offset), each [B*T], of T new tokens a slot from `start`
    [B]: where write_paged_layer and write_index_layer scatter."""
    pos = start[:, None] + jnp.arange(T)[None, :]          # [B,T] absolute
    page_idx = jnp.take_along_axis(page_table, pos // page, axis=1)  # [B,T]
    # Prefill buckets pad T past the true prompt, so pos can exceed the
    # table row's capacity. Route those positions to the null page
    # explicitly rather than relying on take_along_axis's out-of-bounds
    # fill (INT32_MIN) being dropped by the scatter.
    page_idx = jnp.where(pos < page_table.shape[1] * page, page_idx,
                         num_pages - 1)
    if active is not None:
        page_idx = jnp.where(active[:, None], page_idx, num_pages - 1)
    return page_idx.reshape(-1), (pos % page).reshape(-1)


def write_paged_layer(k_pages: jax.Array, v_pages: jax.Array,
                      page_table: jax.Array, k: jax.Array, v: jax.Array,
                      start: jax.Array,
                      active: Optional[jax.Array] = None,
                      k_scale_pages: Optional[jax.Array] = None,
                      v_scale_pages: Optional[jax.Array] = None):
    """Scatter new tokens into one layer's page pool.

    k_pages/v_pages: [P, Kv, page, H]; k/v: [B, T, Kv, H] (T new tokens per
    slot); start: [B] first absolute position of each slot's new tokens.
    Inactive slots' writes are redirected to the null page. Positions past
    a slot's allocated pages must not occur for active slots (the host
    allocator guarantees capacity before scheduling the step).

    Quantized pools (int8 codes + scale pools [P, Kv*page]): k/v arrive
    as floats and are quantized per-vector on the way in. Returns
    (k_pages, v_pages, k_scale_pages, v_scale_pages) — scales None when
    the pool is float.

    Mixed-dispatch contract (ISSUE 18): inside a fused mixed block the
    per-slot `start` is the slot's live cursor/length carry and T is
    the chunk width C — a decode-phase lane writes its one token at
    start=length with the chunk tail masked inactive, a prefill-phase
    lane writes its next C prompt tokens at start=cursor. Both reduce
    to exactly this scatter; no other write primitive exists for the
    fused path.
    """
    Pp, Kv, page, H = k_pages.shape
    B, T = k.shape[0], k.shape[1]
    flat_pages, flat_off = _write_targets(page_table, Pp, page, start, T,
                                          active)
    if k_scale_pages is not None:
        kq, ks = quantize_kv(k)   # codes [B,T,Kv,H], scales [B,T,Kv]
        vq, vs = quantize_kv(v)
        k_pages = k_pages.at[flat_pages, :, flat_off].set(
            kq.reshape(B * T, Kv, H))
        v_pages = v_pages.at[flat_pages, :, flat_off].set(
            vq.reshape(B * T, Kv, H))
        # flat scale dim is kv-major: col = kv*page + offset
        cols = jnp.arange(Kv)[None, :] * page + flat_off[:, None]  # [BT,Kv]
        k_scale_pages = k_scale_pages.at[flat_pages[:, None], cols].set(
            ks.reshape(B * T, Kv))
        v_scale_pages = v_scale_pages.at[flat_pages[:, None], cols].set(
            vs.reshape(B * T, Kv))
        return k_pages, v_pages, k_scale_pages, v_scale_pages
    kf = k.reshape(B * T, Kv, H).astype(k_pages.dtype)
    k_pages = k_pages.at[flat_pages, :, flat_off].set(kf)
    if v_pages is not None:     # a latent pool has no values of its own
        vf = v.reshape(B * T, Kv, H).astype(v_pages.dtype)
        v_pages = v_pages.at[flat_pages, :, flat_off].set(vf)
    return k_pages, v_pages, None, None


def write_index_layer(ki_pages: jax.Array, page_table: jax.Array,
                      ki: jax.Array, start: jax.Array,
                      active: Optional[jax.Array] = None) -> jax.Array:
    """write_paged_layer for the third kind of row: index keys ki
    [B, T, Hi] into one layer's ki_pages [P, 1, page, index_row], at the
    pages and offsets their keys and values go to."""
    Pp, _, page, width = ki_pages.shape
    B, T = ki.shape[:2]
    pages, off = _write_targets(page_table, Pp, page, start, T, active)
    return ki_pages.at[pages, 0, off].set(
        _in_lanes(ki.reshape(B * T, -1), width).astype(ki_pages.dtype))


def _table_pages(pages: jax.Array, page_table: jax.Array, layer):
    """pages[page_table] of one layer: `pages` is that layer's
    [P, ...] (layer None) or the whole [L, P, ...] pool, indexed by
    layer and page in ONE gather, so that no layer is cut out first."""
    return pages[page_table] if layer is None else pages[layer, page_table]


@jax.named_scope("kv_gather")
def gather_paged_layer(pages: jax.Array, page_table: jax.Array,
                       layer=None) -> jax.Array:
    """One layer's pages -> contiguous [B, S_max, Kv, H] view (XLA
    Gather). pages: [P, Kv, page, H], or with `layer` [L, P, Kv, page, H]."""
    Kv, page, H = pages.shape[-3:]
    B, max_pages = page_table.shape
    out = _table_pages(pages, page_table, layer)  # [B, mp, Kv, page, H]
    out = out.transpose(0, 1, 3, 2, 4)      # [B, max_pages, page, Kv, H]
    return out.reshape(B, max_pages * page, Kv, H)


@jax.named_scope("kv_gather")
def gather_paged_layer_q(pages: jax.Array, scale_pages: jax.Array,
                         page_table: jax.Array, layer=None):
    """Quantized gather: codes [B, Kv, S, H] + scales [B, Kv, S] — the
    kv-major order models.common.attend expects for int8 caches.
    `layer`: as gather_paged_layer's."""
    Kv, page, H = pages.shape[-3:]
    B, max_pages = page_table.shape
    codes = _table_pages(pages, page_table, layer)  # [B, mp, Kv, page, H]
    codes = codes.transpose(0, 2, 1, 3, 4).reshape(B, Kv, max_pages * page, H)
    sc = _table_pages(scale_pages, page_table, layer)   # [B, mp, Kv*page]
    sc = sc.reshape(B, max_pages, Kv, page).transpose(0, 2, 1, 3)
    return codes, sc.reshape(B, Kv, max_pages * page)


# ---------------------------------------------------------------------------
# Write-combined decode window (serving hot path)
#
# Window-off, every step of a fused decode/spec block scatters its fresh
# K/V into the FULL [L, P, Kv, page, H] page pool via write_paged_layer —
# and because the pool rides the block scan's carry, XLA cannot alias the
# scatter in place: each step pays a pool-sized copy per pool tensor (the
# same term models/common.py's fused-generate window retired for the
# contiguous cache). With kv_write_combine the pool is READ-ONLY inside the
# block: fresh K/V stages into a small per-slot window [L, S, Kv, W, H]
# riding the scan carry, attention reads pool + window, and the window
# flushes into the pool once per drain, in place, page by staged page
# (flush_paged_window).
#
# The window rides every LAYER scan whole too, in the scan's carry, and is
# read by layer index as the pool is: a layer stages its rows INTO the
# carried leaf (stage_window_layer) and its readers take (layer, slot)'s
# block of it. As scanned inputs and stacked outputs the leaves were read
# and written whole in every step to stage one entry a decode row (a
# scan's stacked output is a fresh buffer; PERF.md, PR 47). Where
# kernels are on the writer is a Mosaic call (ops/window_stage.py), because
# the reader is: XLA's scatter of single positions and a Mosaic call want
# two layouts of one buffer, and XLA then relays the whole leaf between
# them in every layer.
#
# The window stores the pool's EXACT representation (int8 codes + f32
# scales when the pool is quantized, pool dtype otherwise), and the
# non-kernel read path INSERTS the window entries into the gathered pool
# view at their absolute positions rather than concatenating a segment:
# the attend() call then runs on an element-wise identical operand set to
# the window-off write-then-gather path, so greedy serving outputs are
# byte-identical in both modes BY CONSTRUCTION (the parity contract
# tests/test_sched.py pins). Spec rollback is exact the same way: a
# rejected draft's K/V sits past win_len, is never attendable (insert
# positions >= any valid query) and is never flushed — the flushed pool
# never holds stale speculative state.
# ---------------------------------------------------------------------------


class KVWindow(NamedTuple):
    """Staged-but-unflushed K/V for every slot, all layers.

    k/v: [L, S, Kv, W, H] in the pool's representation (int8 codes when
    the pool is quantized, else the pool dtype; [L, S, 1, W, Kv*H]
    beside a token-major pool); k/v_scale [L, S, W/ws, Kv*ws] f32 iff
    quantized: the ws = window_step(W) positions the paged kernel
    multiplies in one step of its window segment are ONE flat kv-major
    row (column kv*ws + w), as a page's scales are one row of the pool's,
    so that the kernel reads them where they lie (scales_by_head is the
    [.., Kv, W] view XLA's readers take of a few slots' rows). Entry w
    of slot s sits at absolute position
    lengths[s] + w of that slot's sequence, where lengths is the
    FLUSHED pool length; a separate win_len [S] vector (ridden through
    the block-scan carry beside this buffer, not stored here — it is
    shared by all layers) counts the valid entries per slot. Contents
    past win_len are stale garbage: masking, never zeroing, is the
    correctness mechanism (the buffer is recycled across blocks without
    a clear, like every other pool in this codebase)."""

    k: jax.Array
    v: Optional[jax.Array]           # None beside a latent pool
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    ki: Optional[jax.Array] = None   # [L, S, 1, W, Hi] iff the pool has
                                     # index keys (PagedKVCache.ki_pages)

    @property
    def width(self) -> int:
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_kv_window(cache: PagedKVCache, width: int,
                   shardings: Optional[KVWindow] = None) -> KVWindow:
    """Allocate a window sized to `width` staged tokens per slot, in the
    pool's representation (and, given `shardings` from
    parallel/partition.py kv_window_specs, in its mesh layout)."""
    _, _, Kv, _, H = cache.k_pages.shape
    L, S = cache.num_layers, cache.num_slots
    shape = (L, S, Kv, width, H)
    quantized, dtype = cache.quantized, cache.k_pages.dtype
    values = cache.v_pages is not None

    ws = window_step(width)
    sshape = (L, S, width // ws, Kv * ws)

    def build():
        if quantized:
            return KVWindow(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                k_scale=jnp.zeros(sshape, jnp.float32),
                v_scale=jnp.zeros(sshape, jnp.float32))
        ki = None if cache.ki_pages is None else jnp.zeros(
            (L, S, 1, width, cache.ki_pages.shape[-1]), cache.ki_pages.dtype)
        return KVWindow(k=jnp.zeros(shape, dtype),
                        v=jnp.zeros(shape, dtype) if values else None, ki=ki)

    return jax.jit(build, out_shardings=shardings)()


def scales_by_head(scales: jax.Array, kv_heads: int) -> jax.Array:
    """A window's scales as they are stored, [.., W/ws, Kv*ws] (KVWindow),
    seen a head a row: [.., Kv, W]. For the few rows XLA's readers take
    (a chunk's slot, a flush's run); the paged kernel reads them as
    stored."""
    *lead, steps, flat = scales.shape
    ws = flat // kv_heads
    a = scales.reshape(*lead, steps, kv_heads, ws)
    return jnp.moveaxis(a, -3, -2).reshape(*lead, kv_heads, steps * ws)


def scales_by_step(scales: jax.Array) -> jax.Array:
    """scales_by_head's inverse: [.., Kv, W] as the window stores them."""
    *lead, Kv, W = scales.shape
    ws = window_step(W)
    a = scales.reshape(*lead, Kv, W // ws, ws)
    return jnp.moveaxis(a, -3, -2).reshape(*lead, W // ws, Kv * ws)


def window_runs(slot, first, count, width: int) -> jax.Array:
    """The table [3, R] of R runs of staged rows (ops/window_stage.py):
    each run's slot, its first window index and how many of its
    consecutive entries land: `count` of them, less what would pass the
    window's width (a write at index W or more is dropped)."""
    n = jnp.clip(jnp.minimum(count, width - first), 0)
    return jnp.stack([slot, first, n]).astype(jnp.int32)


@jax.named_scope("kv_window_write")
def stage_window_layer(window: KVWindow, layer, k, v, ki, slot, idx, runs,
                       widths, use_kernel: bool,
                       looped: bool = True) -> KVWindow:
    """Stage one layer's fresh rows INTO the whole window, which rides
    the layer scan's carry: `L x rows x Kv x H` values move, the leaves
    stay where they are.

    window: the leaves [L, S, Kv, W, H]; layer: the one written (a
    traced scalar); k/v [B, T, Kv, H] floats, N = B*T rows (v None
    beside a latent pool), ki [B, T, Hi] the index keys of a model with
    an indexer (else None); row r belongs to slot[r] and lands at
    window index idx[r], an index of W or more dropping its write;
    quantized on the way in when the
    window holds int8 codes (the pool's representation, so a later
    flush copies bytes verbatim and in-window attention dequantizes
    exactly like the pool read would). Indices never collide with valid
    entries (writes start AT the staged count), so dead slots need no
    masking: their count never advances and their staged bytes stay
    unattendable garbage.

    runs, widths: the same rows as runs of consecutive entries of one
    slot (window_runs; widths their static lengths, group by group), as
    the Mosaic writer takes them where kernels are on
    (ops/window_stage.py: a row is a read-modify-write of its tile
    group, because the kernels that READ the window want their layout of
    it). Kernels off, or a leaf the writer cannot take, it is XLA's
    scatter at (layer, slot, :, idx): reader and writer are then both
    XLA's and the carry is updated in place. looped: False from a run
    of ONE layer, which is no loop to XLA (ops/window_stage.py holds an
    int8 window's scales to HBM inside loops only)."""
    N = slot.shape[0]
    heads, width = window.k.shape[2], window.k.shape[4]
    # the heads as the window lays them (a token-major row: one of Kv*H)
    k, v = (None if a is None else a.reshape(N, heads, width)
            for a in (k, v))
    rows, scale_rows = [], []
    if window.quantized:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        scale_rows = [ks, vs]
    if ki is not None:
        ki = _in_lanes(ki, window.ki.shape[4])
    for leaf, a in ((window.k, k), (window.v, v), (window.ki, ki)):
        if leaf is not None:
            rows.append(a.reshape(N, *leaf.shape[2:3],
                                  leaf.shape[4]).astype(leaf.dtype))
    # the row leaves, then an int8 window's scales (which has no index
    # keys): window_leaves' order
    leaves = window_leaves(window)
    n = len(rows)
    staged = stage_window_sharded(
        leaves[:n], leaves[n:], rows, scale_rows, layer, runs,
        widths, looped) if use_kernel else None
    if staged is not None:
        return window_leaves(window, (*staged[0], *staged[1]))
    ws = window_step(window.width)
    cols = jnp.arange(heads)[None, :] * ws + (idx % ws)[:, None]  # [N, Kv]
    return window_leaves(window, tuple(
        leaf.at[layer, slot, :, idx].set(a, mode="drop") if leaf.ndim == 5
        else leaf.at[layer, slot[:, None], (idx // ws)[:, None], cols].set(
            a, mode="drop")
        for leaf, a in zip(leaves, (*rows, *scale_rows), strict=True)))


def window_rows(leaf, layer, slots=None):
    """One layer's entries of a window leaf [L, S, ...] as XLA's readers
    take them: every slot's [S, ...] (slots None), or the rows' own,
    [B, ...] for slots [B], each ONE dynamic slice by layer and slot
    together (a chunk reads its one slot's run, not the layer's)."""
    if leaf is None:
        return None
    if slots is None:
        return lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    rest = (0,) * (leaf.ndim - 2)
    return jnp.concatenate([
        lax.dynamic_slice(leaf, (layer, slots[b], *rest),
                          (1, 1, *leaf.shape[2:]))[0]
        for b in range(slots.shape[0])])


@jax.named_scope("kv_gather")
def insert_window_view(view, wl, base, ring: bool = False):
    """Insert a layer's window entries into the gathered float view at
    their absolute positions: view [B, S_max, Kv, H], wl [S, Kv, W, H],
    base [S] flushed length per slot. Entries past a slot's valid count
    land at positions no causal query reaches (>= the query's own
    position) and positions past S_max drop, so the whole window inserts
    unconditionally — the result is element-wise identical to the
    window-off path's written pool view, which is the byte-parity
    contract. ring: the view is a ring's cells (ring_positions), and an
    entry lands in the cell of its position."""
    B = view.shape[0]
    W = wl.shape[2]
    pos = base[:, None] + jnp.arange(W)[None, :]        # [B, W]
    if ring:
        pos = pos % view.shape[1]
    return view.at[jnp.arange(B)[:, None], pos].set(
        wl.transpose(0, 2, 1, 3), mode="drop")


@jax.named_scope("kv_gather")
def insert_window_view_q(codes, scales, wl, wsl, base):
    """Quantized twin: codes [B, Kv, S_max, H] + scales [B, Kv, S_max]
    gain the window's codes wl [S, Kv, W, H] + scales wsl [S, Kv, W] at
    absolute positions."""
    B = codes.shape[0]
    W = wl.shape[2]
    rows = jnp.arange(B)[:, None]
    pos = base[:, None] + jnp.arange(W)[None, :]
    codes = codes.at[rows, :, pos].set(wl.transpose(0, 2, 1, 3),
                                       mode="drop")
    scales = scales.at[rows, :, pos].set(wsl.transpose(0, 2, 1),
                                         mode="drop")
    return codes, scales


def _staged_runs(cache: PagedKVCache, win_len, W: int):
    """The staged entries as (slot, page) RUNS: a slot's entries lie at
    consecutive positions from its flushed length, so they fall into one
    run a page they touch (a decode slot's few tokens one page or two, a
    chunk's 128 eight or nine). Returns the number of runs (a value:
    it follows what was staged) and an int32 table [R, 4], R the static
    most (every slot's whole window, one page more for a start inside a
    page), of which the first `runs` rows are real: slot, physical
    page, the window index that lies at the page's row 0 (negative
    where the run starts inside the page) and the slot's count of
    entries that land. Positions at or past max_pages x page are not
    among them: they are dropped, as the table holds no page for them."""
    S = win_len.shape[0]
    page, mp = cache.page_size, cache.page_table.shape[1]
    ln = cache.lengths
    n = jnp.clip(mp * page - ln, 0, win_len)               # [S] that land
    first = ln // page
    runs = jnp.where(n > 0, (ln + n - 1) // page - first + 1, 0)
    ends = jnp.cumsum(runs)
    j = jnp.arange(S * (-(-W // page) + 1), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, j, side="right",
                                        method="compare_all"), S - 1)
    lp = first[slot] + j - (ends - runs)[slot]             # logical page
    pg = cache.page_table[slot, jnp.clip(lp, 0, mp - 1)]
    cols = [slot, pg, lp * page - ln[slot], n[slot]]
    if cache.by_kind:
        # the same run in the sliding layers' ring: a fifth column
        cols.append(cache.ring_table[slot, lp % cache.ring_table.shape[1]])
    return ends[-1], jnp.stack(cols, axis=1).astype(jnp.int32)


def flush_paged_window(cache: PagedKVCache, window: KVWindow, win_len):
    """Flush every slot's staged window entries into the page pool, in
    place: a loop over the (slot, page) runs that were staged
    (_staged_runs), each reading ONE page of every pool tensor (all
    layers: [L, 1, Kv, page, H], 512 KB of int8 for Mistral-7B; the
    index keys of a model with an indexer are one more such tensor),
    replacing the run's rows from the window and writing the page back
    with a dynamic-update-slice of the carried pool. Under donation that
    is an in-place write on the chip, and a whole page is whole tiles
    whatever the row's packing (an int8 row is a quarter of a packed
    word), so the one algorithm serves float and int8 pools and their
    scale pools ([L, 1, Kv*page], the page's row). The cost follows the
    runs, never the pool's size or the window's: nothing is scattered,
    transposed or copied at the pool's size (a scatter that indexes the
    page and the in-page offset makes XLA move the layers and the
    offset beside the page dim and back: two copies and two relayouts
    of the whole pool a flush on the chip, PERF.md PR 35).

    Entries at or past win_len (dead-step repeats, rejected speculative
    drafts) are in no run and are written nowhere — the flushed pool
    never holds them, which is what makes spec rollback exact for
    flushed state. Under mixed dispatch (ISSUE 18) prefill-chunk K/V
    stages through this same window: win_len for a prefill-phase slot
    grows by chunk widths rather than 1 per step, and an admission
    seeds the slot's win_len to 0 (the freed slot was flushed at its
    drain), so a fused block's staged prompt entries can never
    interleave with a predecessor's. Returns (cache with lengths
    advanced by win_len, zeroed win_len, flushed token count
    [scalar]).

    A cache by kind writes each run twice: the full layers' rows into
    their page of k_pages / v_pages and the sliding layers' into their
    ring's (the window holds every attention layer: a run's rows are
    cut from it whole, [L, 1, Kv, seg, H], and each pool takes the
    layers of its kind).
    """
    L, page = window.k.shape[0], cache.page_size
    W, Kv = window.width, window.k.shape[2]
    seg = min(page, W)          # window rows one run can take
    ws = window_step(W)
    steps = min(W // ws, (seg + ws - 2) // ws + 1)  # a run's, of scales
    runs, table = _staged_runs(cache, win_len, W)
    rows = jnp.arange(page, dtype=jnp.int32)
    staged_leaves = window_leaves(window)
    # (the page's column in the run table, the window's layers a pool
    # holds) a pool leaf: all of them, or a kind's
    kinds = [(1, None)] * len(staged_leaves)
    if cache.by_kind:
        kinds = [(1, cache.full_layers)] * 2 + [(4, cache.ring_layers)] * 2
        staged_leaves = staged_leaves * 2

    def body(i, pools):
        run = lax.dynamic_slice(table, (i, 0), (1, table.shape[1]))[0]
        slot, _, base, n = run[:4]
        # the page's row r holds window index base + r: a slice of the
        # window from the nearest index that keeps it inside, rolled so
        # that its rows meet the page's
        keep = (base + rows >= 0) & (base + rows < n)           # [page]
        at = jnp.clip(base, 0, W - seg)

        def merge(pool, staged, kind):
            """pool [Lp, P, ...] with the run's rows of its page taken
            from staged [L, S, Kv, W, ...], the layers `kind` names."""
            tail = staged.shape[4:]
            pg, layers = run[kind[0]], kind[1]
            old = lax.dynamic_slice(pool, (0, pg) + (0,) * (pool.ndim - 2),
                                    (pool.shape[0], 1) + pool.shape[2:])
            src, mine, first = staged, slot, at
            if staged.ndim == 4:
                # an int8 window's scales, a step a row (KVWindow): the
                # steps the run lies in, seen a head a row
                j = jnp.clip(at // ws, 0, W // ws - steps)
                src = scales_by_head(lax.dynamic_slice(
                    staged, (0, slot, j, 0), (L, 1, steps, Kv * ws)), Kv)
                mine, first = 0, at - j * ws
            new = lax.dynamic_slice(
                src, (0, mine, 0, first) + (0,) * len(tail),
                (L, 1, src.shape[2], seg) + tail)
            if layers is not None:
                new = new[layers]
            if seg < page:
                new = jnp.pad(new, [(0, 0)] * 3 + [(0, page - seg)]
                              + [(0, 0)] * len(tail))
            new = jnp.roll(new, at - base, axis=3)
            mask = keep.reshape((page,) + (1,) * len(tail))
            # a scale pool's row is [Kv*page], kv-major: the same rows
            new = jnp.where(mask, new, old.reshape(new.shape))
            return lax.dynamic_update_slice(
                pool, new.reshape(old.shape), (0, pg) + (0,) * (pool.ndim - 2))

        return tuple(merge(p, w, k)
                     for p, w, k in zip(pools, staged_leaves, kinds))

    mine = pool_leaves(cache)
    if cache.by_kind:
        mine += (cache.k_ring, cache.v_ring)
    pools = lax.fori_loop(0, runs, body, mine)
    if cache.by_kind:
        cache = cache._replace(k_ring=pools[2], v_ring=pools[3])
        pools = pools[:2]
    cache = pool_leaves(cache, pools)._replace(
        lengths=cache.lengths + win_len)
    return cache, jnp.zeros_like(win_len), win_len.sum()


# ---------------------------------------------------------------------------
# Paged forward pass (reference path; Pallas decode kernel lives in ops/)
# ---------------------------------------------------------------------------

#: what paged_forward and paged_forward_window are to a model whose
#: streams hold a recurrent state, or whose layers run as runs of two
#: feed-forward shapes over a latent pool: the packed mixed step
#: carries both, and what still calls these two (the speculative
#: block's verify, tools that read a lane-wide step) does not
LANE_WIDE = ("the lane-wide forward (paged_forward, paged_forward_window: "
             "the speculative block's verify)")


def by_kind_unsupported(cache: PagedKVCache, what: str) -> None:
    """Refuse a cache that keeps the sliding layers' rows in a ring of
    their own (PagedKVCache.by_kind) on a path that reads every layer
    under the one page table."""
    if cache.by_kind:
        raise NotImplementedError(
            f"{what} reads every layer's rows under ONE page table; this "
            "cache keeps the sliding layers' in a ring of their own "
            "(cache/paged.py ring_pages): not supported for this cache")


def _settled(*view):
    """The gathered view as attend() reads it, closed to fusion with
    what built it. Window on and off build the same elements by
    different routes (gather + insert against write + gather); left
    open, XLA fuses each route into attend's products its own way and
    the two programs part in the last digits, enough to move an int8
    scale downstream. Behind the barrier attend compiles from the same
    operands in both: the parity the window promises, to the bit."""
    return lax.optimization_barrier(view)


def _row_addresses(page_table: jax.Array, num_pages: int, page: int,
                   layer) -> jax.Array:
    """[B, S_max] int32: for each position of each stream's table the
    ROW of a token-major pool seen flat [L*P*page, Kv*H] that holds it in
    `layer`. The table broadcast over a page's offsets: no lookup."""
    B, mp = page_table.shape
    addr = (layer * num_pages + page_table)[:, :, None] * page \
        + jnp.arange(page, dtype=jnp.int32)
    return addr.reshape(B, mp * page)


def _pool_rows(pages: jax.Array, row: jax.Array) -> jax.Array:
    """Token rows of a token-major pool by address: pages
    [L, P, 1, page, R] (R = Kv*H: a token's KV heads contiguous), row
    [B, K] (_row_addresses) -> [B, K, R]. The pool is seen as its rows
    [L*P*page, R] (free: the two minor dims are whole tiles) and ONE
    `take` reads the B x K rows the streams selected and no others.
    XLA's gather pays by the row, about 11 ns up to 1 KB: a token a row
    is a quarter of the rows a (token, KV head) a row was (PERF.md PRs
    36, 37). An address below 0 (not in the pool: masked by the caller)
    reads row 0: clipped, where take's default would pass both results
    through a select again. The take is of the addresses FLAT: its
    result [B*K, R] is a shape the benchmark's reader of the sparse
    path knows by its first dim (servebench/sparse_peaks.py)."""
    L, P, _, page, R = pages.shape
    got = jnp.take(pages.reshape(L * P * page, R), row.reshape(-1), axis=0,
                   mode="clip")
    return got.reshape(*row.shape, R)


#: the masked read (ops/sparse_attention.py) serves a table of up to
#: this many times index_topk positions; a longer one takes the gather.
#: The masked read moves every LIVE row, the gather the SELECTED ones.
#: On a v5e at Keye's geometry (`tools/chip_kernels.py --only
#: sparse_cell`, PERF.md PR 51) the walk costs 2.9-3.2 ns a live row
#: (671 us for 32 slots of 7,168) and the gather's side of the branch
#: (two gathers, the sort's payload, the window's lookups, the product
#: over what was gathered) 27 ns a selected row: at live = 3.5 x topk
#: the masked branch still reads 1.10 ms under the gather's, and the two
#: would meet near live = 9 x topk, where no cell or configuration has a
#: table. 4 is inside what was measured
MASKED_READ_SPAN = 4


def _index_selection(index, win, layer, *, page_table, positions, mask,
                     active, topk: int, select: str, scatter: bool,
                     use_kernel: bool):
    """The read side of a sparse-attention indexer, for keys and values
    (sparse_paged_attend) and for latent rows (latent_paged_attend)
    alike: index (qi [B,T,Ni,Hi], w [B,T,Ni], kip [L,P,1,page,index_row])
    as index_proj and the pool give them; win, page_table, positions,
    active as paged_attend's (the window AFTER staging, its index keys
    [L,S,1,W,index_row] among its leaves); mask [B,T,S_max] what each
    query MAY attend. Returns (scores [B,T,S_max], topk, live [B,T]):
    every query's score of every position of its stream's table
    (models.common.index_scores), how many of them it attends, and how
    many it could. A score at a position the mask leaves out is a number
    that means nothing.

    A decode row (T == 1) with kernels on scores its slot's LIVE pages
    where they lie in the pool, and the window's staged keys where they
    lie in the window, through the Pallas call (ops/index_scores.py:
    pool positions up to the FLUSHED length, or with no window up to
    the token just written). Every other program gathers the table's
    index keys to one view of S_max positions (gather_paged_layer) and
    scores the view, the staged keys reaching their positions one of
    two ways:

    scatter, the ONE difference between the two callers, and only on
    the view's path: False inserts the KEYS into the table's view and
    scores the view (Keye's program since PR 36: its window keys' slice
    fuses into the insert); True scores them where they lie and the
    SCORES take their positions, without a write of the keys into the
    view (GLM-5's: XLA cut the layer's slice of the carried window leaf
    out in a fusion of its own, 2 MB a layer, which
    tools/chip_kernels.py's window_moves refuses: PERF.md section 7,
    PR 52). The same scores either way.

    select (tools/sparse_parity.py's controls; "index" everywhere
    else): "all" attends every position, "recent" the last topk in
    place of the indexer's choice."""
    qi, w, kip = index
    B, T, _, Hi = qi.shape
    S_max = mask.shape[-1]
    window = base = slots = None
    if win is not None:
        window, win_len, slots = win
        base = positions[:, 0] - win_len    # flushed pool length per row
    with jax.named_scope("attn_index"):
        if T == 1 and use_kernel and slots is None and paged_index.fits(
                kip, 0 if win is None else window.width):
            lens = positions[:, 0] + 1 if win is None else base
            scores = paged_index.index_scores(
                qi[:, 0], w[:, 0], kip, layer, page_table,
                jnp.where(active, lens, 0),
                None if win is None else window.ki)[:, None]
        else:
            # the stream's index keys as keys are viewed: one KV head,
            # the lanes behind a key cut off (index_row)
            kiv = gather_paged_layer(kip, page_table, layer)[..., :Hi]
            wki = None if win is None else window_rows(
                window.ki, layer, slots)[..., :Hi]         # [B,1,W,Hi]
            if wki is not None and not scatter:
                kiv = insert_window_view(kiv, wki, base)
            scores = index_scores(qi, w, kiv[:, :, 0])     # [B,T,S_max]
            if wki is not None and scatter:
                at = base[:, None] + jnp.arange(wki.shape[2])[None, :]
                scores = scores.at[
                    jnp.arange(B)[:, None, None],
                    jnp.arange(T)[None, :, None],
                    at[:, None, :]].set(index_scores(qi, w, wki[:, 0]),
                                        mode="drop")
    if select == "all":
        scores = jnp.zeros_like(scores)
        topk = S_max
    elif select == "recent":
        scores = jnp.broadcast_to(jnp.arange(S_max, dtype=scores.dtype),
                                  scores.shape)
    return scores, topk, jnp.sum(mask, axis=-1)


def _selection(scores, valid, k: int, use_kernel: bool):
    """models.common.select_mask over the last axis of scores [..., n]:
    `valid` narrowed to its k highest scores, THE SAME positions either
    way. With kernels on, where there is anything to leave out (n > k)
    and the rows fit (ops/select_mask.py fits), the k-th score and its
    tie are found by counting in a Pallas call and the selection comes
    back int32, what the two selecting reads take it in; else lax.top_k
    and a running count give it as bool. The rows go to the call as
    [R, n] and the selection is shaped back after it: neither side of
    the call sees a unit dim before the lanes."""
    n = scores.shape[-1]
    flat = scores.reshape(-1, n)
    if use_kernel and paged_select.fits(flat, k):
        return paged_select.select_mask(
            flat, valid.reshape(-1, n), k).reshape(valid.shape)
    if use_kernel and n > k:
        note_kernel("dense_fallback")
    return select_mask(scores, valid, k)


def _selection_count(live, read, moved):
    """f32 [4], what a selecting read counts for the tick record
    (kv_rows_live / _selected / _moved): the rows that had anything to
    attend, the positions they could attend, the positions they
    ATTENDED and the cached rows the read MOVED for them, summed over
    the rows."""
    count = jnp.stack([jnp.sum(live > 0), jnp.sum(live), jnp.sum(read),
                       jnp.sum(moved)])
    return count.astype(jnp.float32)


def sparse_paged_attend(q, qi, w, kp, vp, kip, layer, *, cfg: ModelConfig,
                        page_table, positions, mask, active=None,
                        use_kernel: bool = False, win=None,
                        select: str = "index"):
    """paged_attend for a model with a sparse-attention indexer: each
    query scores every live position of its stream against the cached
    index keys (models.common.index_scores) and attends the
    cfg.index_topk that score highest. q [B,T,Nq,H]; qi [B,T,Ni,Hi] and
    w [B,T,Ni] from index_proj; kp/vp [L,P,1,page,Kv*H] (token-major:
    pool_row) and kip [L,P,1,page,Hi] the whole pools, `layer` the one
    to read; mask [B,T,S_max] what each query MAY attend (causal,
    live); win as paged_attend's, AFTER staging: the whole window
    (leaves [L,S,1,W,Kv*H], the index keys' [L,S,1,W,Hi]), of which the
    rows' slots' entries of `layer` are read (window_rows). active [B]
    (the kernel's read alone: a row that is not live reads nothing
    there) and use_kernel as paged_attend's. Returns
    (out [B,T,Nq,H], count f32 [4]): the rows that
    had anything to attend, the positions they could attend, the
    positions they ATTENDED and the rows of keys (and as many of values)
    the read MOVED out of the pool and the window for them, summed over
    the rows.

    A decode row (T == 1) ATTENDS ONLY WHAT IT SELECTED, and there are
    two ways to move those rows, told apart by the table's span against
    index_topk, which the program's shapes show (MASKED_READ_SPAN): (1)
    where kernels are on and the table is a few times index_topk, nearly
    every live page holds a selected row: the Pallas kernel
    (ops/sparse_attention.py) walks the slot's LIVE pages, a page a
    copy, and the selection (_selection) joins its length mask; (2) a
    longer table, or kernels off: the selected rows of keys and values
    out of the pool, a token a row, by the row's address
    (_row_addresses, _pool_rows), beside the window's few staged
    entries, which are read whole and masked to the selection. The same
    sum either way. A chunk's rows (T > 1) share one stream's prefix:
    it is read once, whole, and masked row by row (_selection), which
    is the same mathematics and cheaper than T gathers. So is any
    program without the kernel whose whole context is no longer than
    index_topk.

    select: tools/sparse_parity.py's controls (_index_selection)."""
    B, T = q.shape[:2]
    page = kp.shape[3]
    S_max = page_table.shape[1] * page
    base = None
    if win is not None:
        window, win_len, slots = win
        wk, wv = (window_rows(a, layer, slots) for a in (window.k, window.v))
        base = positions[:, 0] - win_len    # flushed pool length per row
    scores, topk, live = _index_selection(
        (qi, w, kip), win, layer, page_table=page_table,
        positions=positions, mask=mask, active=active,
        topk=cfg.index_topk, select=select, scatter=False,
        use_kernel=use_kernel)
    if T == 1 and use_kernel and S_max <= MASKED_READ_SPAN * cfg.index_topk \
            and (win is None or slots is None) and sparse_attention.fits(
                kp, q.shape[-1], 0 if win is None else window.width):
        sel = _selection(scores[:, 0], mask[:, 0], topk,
                         use_kernel)                       # [B,S_max]
        # pool rows up to the FLUSHED length and the window's staged run
        # with the token just staged (the window whole: the kernel reads
        # the layer's blocks of it, under the selection at the staged
        # rows' positions), or the pool alone with the token just written
        lens = (jnp.where(active, base, 0), sel, window.k, window.v,
                jnp.where(active, win_len + 1, 0)) if win is not None \
            else (jnp.where(active, positions[:, 0] + 1, 0), sel)
        out = sparse_attention.sparse_attention(
            q[:, 0], kp, vp, layer, page_table, *lens)[:, None]
        read = jnp.sum(sel, axis=-1)[:, None]
        moved = live
    elif T > 1 or S_max <= topk:
        sel = _selection(scores, mask, topk, use_kernel)
        with jax.named_scope("attn_sparse"):
            ck = gather_paged_layer(kp, page_table, layer)
            cv = gather_paged_layer(vp, page_table, layer)
            if win is not None:
                ck = insert_window_view(ck, wk, base)
                cv = insert_window_view(cv, wv, base)
            out = attend_token_rows(
                q, *_settled(ck[:, :, 0], cv[:, :, 0]), sel.astype(bool))
        read = jnp.sum(sel, axis=-1)
        moved = S_max * (live > 0)      # the slot's whole view
    else:
        # a position's pool row rides the selection as the sort's
        # payload (a lookup of the K selected positions in the table
        # afterwards is a gather of K scalars a stream, at a row's cost
        # each); -1: staged in the window, not in the pool
        addr = _row_addresses(page_table, kp.shape[1], page, layer)
        if win is not None:
            addr = jnp.where(jnp.arange(S_max)[None, :] < base[:, None],
                             addr, -1)
        row, ok, sel = select_topk(scores[:, 0], mask[:, 0], topk,
                                   with_mask=win is not None, payload=addr)
        with jax.named_scope("attn_sparse"):
            ok = ok & (row >= 0)
            kg = _pool_rows(kp, row)                       # [B,K,Kv*H]
            vg = _pool_rows(vp, row)
            if win is not None:
                # the window's W staged entries ride whole behind the
                # selected rows, masked to the selection; token-major as
                # the pool's rows are, so this is no relayout
                wpos = base[:, None] + jnp.arange(wk.shape[2])[None, :]
                wsel = jnp.take_along_axis(
                    sel, jnp.minimum(wpos, S_max - 1), axis=1) \
                    & (wpos < S_max)
                kg = jnp.concatenate([kg, wk[:, 0]], axis=1)
                vg = jnp.concatenate([vg, wv[:, 0]], axis=1)
                ok = jnp.concatenate([ok, wsel], axis=1)
            out = attend_token_rows(q, kg, vg, ok[:, None])
        read = moved = jnp.sum(ok, axis=-1)[:, None]
    return out, _selection_count(live, read, moved)


def latent_paged_attend(q, kp, layer, *, cfg: ModelConfig, page_table,
                        positions, mask, active, use_kernel: bool,
                        win=None, index=None, select: str = "index"):
    """paged_attend for a latent-attention model, the ABSORBED read
    (models/common.py): q [B,T,Nq,Rp] latent_queries' of these rows, in
    the pool's lanes; kp [L,P,1,page,Rp] the WHOLE pool of rows (there
    is no value pool) and `layer` the one to read; page_table,
    positions, mask, active as paged_attend's; win as paged_attend's,
    AFTER staging (the window's one leaf of rows [L,S,1,W,Rp]).
    Returns (o' [B,T,Nq,kv_lora_rank], count f32 [1]): the cached rows
    the DECODE rows read (a live row at position p reads p + 1, itself
    among them; a chunk's rows count nothing), what the tick record's
    `latent_rows` sums over the layers.

    index (a model with a sparse-attention indexer beside its latent
    rows): (qi, w, kip) as sparse_paged_attend takes them, the window's
    index keys in `win`. Every query scores its stream's live positions
    (_index_selection) and `mask` narrows to the cfg.index_topk that
    score highest (_selection) before either read below: a decode row's
    kernel walks the slot's LIVE pages and takes the selection as a
    mask (ops/latent_attention.py latent_select_attention), a chunk's
    rows and every row with kernels off attend the gathered view under
    it. count is then sparse_paged_attend's f32 [4] (_selection_count;
    MOVED: the live rows through the kernel, the slot's whole view
    through the gather). select: the parity tool's controls
    (_index_selection).

    A decode row (T == 1) reads its stream's live pages ONCE through
    the Pallas kernel (ops/latent_attention.py) where kernels are on;
    a chunk's rows (T > 1) share one stream's context, which is
    gathered whole and attended by latent_attend, as is every row where
    kernels are off. Either way no head's keys or values of the
    context exist: scores and sums are over the rows as cached."""
    T = q.shape[1]
    start = positions[:, 0]
    base = None
    if win is not None:
        window, win_len, slots = win
        base = start - win_len      # flushed pool length per row
    if index is not None:
        scores, topk, live = _index_selection(
            index, win, layer, page_table=page_table, positions=positions,
            mask=mask, active=active, topk=cfg.index_topk, select=select,
            scatter=True, use_kernel=use_kernel)
        mask = _selection(scores, mask, topk, use_kernel)
    out = None
    if use_kernel and T == 1 and latent_attention.fits(
            kp, cfg.kv_lora_rank, index is not None,
            0 if win is None else window.width):
        # pool rows up to the FLUSHED length and the window's staged run
        # with the token just staged (the window whole: the kernel reads
        # the layer's block of it), or the pool alone with the token
        # just written
        lens = (jnp.where(active, base, 0), window.k,
                jnp.where(active, win_len + 1, 0)) if win is not None \
            else (jnp.where(active, start + 1, 0),)
        if index is None:
            out = latent_attention.latent_attention(
                q[:, 0], kp, layer, page_table, *lens,
                rank=cfg.kv_lora_rank, scale=cfg.attn_scale)
        else:
            out = latent_attention.latent_select_attention(
                q[:, 0], kp, layer, page_table, lens[0], mask[:, 0],
                *lens[1:], rank=cfg.kv_lora_rank, scale=cfg.attn_scale)
        out = out[:, None]
        moved = None if index is None else live
    if out is None:
        if use_kernel and T == 1:
            note_kernel("dense_fallback")
        rows = gather_paged_layer(kp, page_table, layer)    # [B,S_max,1,Rp]
        if win is not None:
            rows = insert_window_view(
                rows, window_rows(window.k, layer, slots), base)
        out = latent_attend(q, *_settled(rows[:, :, 0]), mask.astype(bool),
                            cfg)
        moved = None if index is None else mask.shape[-1] * (live > 0)
    if index is not None:
        return out, _selection_count(live, mask, moved)
    read = jnp.sum(jnp.where(active, start + 1, 0)) if T == 1 else 0
    return out, jnp.asarray(read, jnp.float32).reshape(1)


def ring_positions(written, cells: int) -> jax.Array:
    """[B, cells] int32: the absolute position whose row each cell of a
    stream's ring holds (cell r = ring entry r // page, offset r % page)
    once `written` [B] positions are in it: the last position below
    `written` that is r modulo the ring's size; negative where none has
    been written."""
    r = jnp.arange(cells, dtype=jnp.int32)[None, :]
    return (written[:, None] - 1 - r) // cells * cells + r


def paged_attend(q, k, v, kp, vp, layer, *, cfg: ModelConfig, page_table,
                 positions, mask, active, use_kernel: bool, fresh: bool,
                 ksp=None, vsp=None, win=None, sliding_window=None,
                 index=None, ring=None):
    """One layer's attention for [B,T] queries whose K/V is already
    written: the dispatch between the paged kernel (T == 1), the flash
    kernels (fresh chunk; warm chunk over the cached prefix) and the
    dense gather, shared by paged_layer_body and the packed mixed step
    (paged_forward_packed) so the two cannot drift. q: [B,T,Nq,H];
    k/v: [B,T,Kv,H] (the fresh projections, read by the flash
    branches only); kp/vp [L,P,Kv,page,H] (ksp/vsp [L,P,Kv*page] iff
    int8): the WHOLE pool, and `layer` the one to attend. The kernel
    takes both as they are; a branch that needs the layer's view in XLA
    indexes layer and page in its own gather (_table_pages). Nobody
    cuts the layer out beforehand: handed to a custom call, such a
    slice is a copy of the layer. page_table: the B rows' own table rows
    [B, max_pages]; win: (window, win_len, slots) AFTER staging: the
    WHOLE window (KVWindow, leaves [L, S, Kv, W, H]: the kernel reads
    (layer, slot)'s block of it, the dense branches slice the rows'
    slots' entries by layer and slot together, window_rows), the B
    rows' staged counts BEFORE it, and their slots [B] (None: the B
    rows are the S slots in order).
    sliding_window: the layer's, out of its pattern (a traced scalar, 0
    = a full layer; None = the model has none): every branch attends
    position j from p only where p - j < sliding_window, the kernels by
    their prefetched scalar, the dense gather by its mask.
    index: (qi, w, kip) for a model with a sparse-attention
    indexer: _layer_open's index queries and weights and the pool of
    index keys (the window's are in `win`).
    Such a layer attends through sparse_paged_attend and nowhere else.
    Returns [B,T,Nq,H]; with `index`, that and sparse_paged_attend's
    count. A latent-attention model (q its absorbed queries, k its
    rows, no v and no vp) attends through latent_paged_attend and
    nowhere else, its `index` with it where it has an indexer, and
    returns that function's pair.
    ring (a cache by kind, window on): None, or the layer's index in
    the WINDOW's leaves, `layer` then being its index in the pool of its
    kind (kp/vp: that pool); page_table is then that kind's too. True
    where the kind is the sliding one: the table is the slot's ring
    (ring_table [B, R]) and a row of position p lies in entry
    (p // page) % R; the kernel walks it so, and the dense branch reads
    the ring's cells whole and masks each by the position it holds
    (ring_positions)."""
    if cfg.is_latent:
        return latent_paged_attend(
            q, kp, layer, cfg=cfg, page_table=page_table,
            positions=positions, mask=mask, active=active,
            use_kernel=use_kernel, win=win, index=index)
    if index is not None:
        qi, w, kip = index
        return sparse_paged_attend(
            q, qi, w, kp, vp, kip, layer, cfg=cfg, page_table=page_table,
            positions=positions, mask=mask, active=active,
            use_kernel=use_kernel, win=win)
    T = q.shape[1]
    quant = ksp is not None
    start = positions[:, 0]
    if win is not None:
        window, win_len, slots = win
        base = start - win_len  # flushed pool length per row
    out = None
    tried_kernel = True
    win_layer, slides = (layer, False) if ring is None else ring
    by_kind = {} if ring is None else dict(win_layer=win_layer, ring=slides)
    if use_kernel and T == 1:
        if win is not None:
            # pool-valid lengths are the FLUSHED base; the staged run
            # (prior entries + the token just staged) rides as a window
            # segment with its own count
            lens = jnp.where(active, base, 0)
            wcnt = jnp.where(active, win_len + T, 0)
            out = paged_attention_sharded(q[:, 0], kp, vp, layer,
                                          page_table, lens, ksp, vsp,
                                          win_k=window.k, win_v=window.v,
                                          win_count=wcnt,
                                          win_k_scale=window.k_scale,
                                          win_v_scale=window.v_scale,
                                          sliding_window=sliding_window,
                                          **by_kind)
        else:
            # lengths INCLUDING the token just written (inactive: 0 ->
            # no pages visited, output discarded)
            lens = jnp.where(active, positions[:, 0] + 1, 0)
            out = paged_attention_sharded(q[:, 0], kp, vp, layer,
                                          page_table, lens, ksp, vsp,
                                          sliding_window=sliding_window)
        out = out[:, None] if out is not None else None
    elif cfg.attn_impl == "flash" and T > 1 and fresh:
        # fresh prefill attends over the just-projected bf16 K/V, so the
        # kernel path is identical for int8 pools
        out = flash_attention_sharded(q, k, v, causal=True,
                                      sliding_window=sliding_window)
    elif cfg.attn_impl == "flash" and T > 1 and win is None:
        # warm chunked prefill (ISSUE 13): the kernel attends the
        # CACHED prefix — the gathered pool view, count-masked per row
        # at the chunk's start (so the chunk's own just-written copy,
        # null-page garbage, and padding rows never contribute) — plus
        # the fresh chunk as causal blocks, one online-softmax state.
        # This replaces the dense O(T*S_max) materialized-scores
        # fallback every warm/chunked/prefix-hit prefill used to pay.
        # (The windowed verify path keeps the dense insert: staged
        # window entries are not in the pool.)
        base = jnp.where(active, start, 0)
        if quant:
            ckg, k_sg = gather_paged_layer_q(kp, ksp, page_table, layer)
            cvg, v_sg = gather_paged_layer_q(vp, vsp, page_table, layer)
            # mirror the chunk's in-pool representation (the dense path
            # reads the quantized write back) — operand-parity with the
            # gather path by construction
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            kf = (kq.astype(jnp.float32) * ksc[..., None]).astype(k.dtype)
            vf = (vq.astype(jnp.float32) * vsc[..., None]).astype(v.dtype)
            out = flash_attention_sharded(
                q, kf, vf, causal=True, prefix_k=ckg, prefix_v=cvg,
                prefix_len=base, prefix_k_scale=k_sg, prefix_v_scale=v_sg,
                sliding_window=sliding_window)
        else:
            ckg = gather_paged_layer(kp, page_table, layer)
            cvg = gather_paged_layer(vp, page_table, layer)
            out = flash_attention_sharded(q, k, v, causal=True,
                                          prefix_k=ckg, prefix_v=cvg,
                                          prefix_len=base,
                                          sliding_window=sliding_window)
    else:
        tried_kernel = False
    if out is None:
        # no mesh axis can shard the kernel operands (or kernels off):
        # dense gather attention, which GSPMD partitions itself. A call
        # site that wanted a kernel says so (ops.record_kernels): on a
        # mesh that should shard it this is a fault, not a choice.
        if tried_kernel:
            note_kernel("dense_fallback")
        if slides:
            # the ring's cells, each masked by the position it holds
            # once the whole window is inserted: what is staged past a
            # row's count lies beyond every query, as ever, and the cell
            # it takes held a row no query reaches any more (ring_pages)
            cells = page_table.shape[1] * kp.shape[3]
            at = ring_positions(base + window.width, cells)[:, None, :]
            p = positions[:, :, None]
            mask = mask[:, :, :1] & (at >= 0) & (at <= p) \
                & (p - at < sliding_window)
        else:
            mask = layer_mask(mask, positions, sliding_window)
        if win is not None:
            wk, wv, wks, wvs = (window_rows(a, win_layer, slots) for a in (
                window.k, window.v, window.k_scale, window.v_scale))
        if quant:
            ck, k_s = gather_paged_layer_q(kp, ksp, page_table, layer)
            cv, v_s = gather_paged_layer_q(vp, vsp, page_table, layer)
            if win is not None:
                Kv = wk.shape[1]
                ck, k_s = insert_window_view_q(
                    ck, k_s, wk, scales_by_head(wks, Kv), base)
                cv, v_s = insert_window_view_q(
                    cv, v_s, wv, scales_by_head(wvs, Kv), base)
            out = attend(q, *_settled(ck, cv), mask, cfg, *_settled(k_s, v_s))
        else:
            ck = gather_paged_layer(kp, page_table, layer)
            cv = gather_paged_layer(vp, page_table, layer)
            if win is not None:
                ck = insert_window_view(ck, wk, base, slides)
                cv = insert_window_view(cv, wv, base, slides)
            out = attend(q, *_settled(ck, cv), mask, cfg)
    return out


def _layer_open(x, lp, cfg: ModelConfig, cos, sin):
    """A layer up to its attention: the weights in the compute dtype,
    the pre-norm, the router's logits where the router stands before
    attention, and the projections, rotated where the layer rotates.
    Returns (lp, q, k, v, route, sliding_window, index, mix, gate): `route`
    (None for most models) is carried across attention to _layer_close,
    the layer's sliding window (None for a model without a pattern)
    goes to paged_attend, and so does `index`, the indexer's (qI, kI, w)
    of these rows (models.common.index_proj; None for a model without
    one), once kI is cached beside k and v; `mix` is the residual
    path's (models.common.stream_read: x is [n,B,T,D] for a model of n
    streams, and None comes back for every other), which _layer_close
    takes, as it does `gate`, the attention's output gate of these rows
    (models.common.attn_gate; None for a model without). With
    _layer_close, the part of a layer that paged_layer_body
    and the packed step (packed_layer) share, so that a change to a
    norm, a projection or the residual path reaches both."""
    lp = jax.tree.map(lambda a: _cast_float(a, jnp.dtype(cfg.dtype)), lp)
    h, mix = stream_read(x, lp, 1, cfg)
    if cfg.is_latent:
        # the absorbed queries and the row a token caches, each laid in
        # the pool's lanes (pool_row: zeros behind the values); no v
        q_nope, q_rope, row, cq = latent_proj(h, lp["attn"], cfg, cos, sin)
        q = latent_queries(q_nope, q_rope, lp["attn"], cfg)
        pad = [(0, 0)] * 3 + [(0, pool_row(cfg)[1] - cfg.latent_row)]
        # the indexer's queries read the query latent, its key and
        # weights the normed input
        index = index_proj(h, lp, cfg, cos, sin, cq) if cfg.has_indexer \
            else None
        return (lp, jnp.pad(q, pad), jnp.pad(row[:, :, None], pad), None,
                None, None, index, mix, None)
    rope, sliding_window = layer_pattern_of(lp.get("pattern"))
    q, k, v = qkv_proj(h, lp["attn"], cfg, cos, sin, rope)
    index = index_proj(x, lp, cfg, cos, sin) if cfg.has_indexer else None
    return (lp, q, k, v, early_router_logits(x, lp, cfg), sliding_window,
            index, mix, attn_gate(h, lp["attn"], cfg))


def _layer_close(x, out, lp, cfg: ModelConfig, mix, route=None, ok=None,
                 gate=None):
    """A layer from its attention's output on: the output projection
    and the feed-forward, each written back onto the residual path
    (models.common.stream_write; ffn_close). mix, route, gate:
    _layer_open's.
    Returns (x, load): with `ok` [B,T], the rows that are real, and a
    model of experts, `load` is what the layer's routing asked of them
    (models.common.expert_load), else None."""
    x = stream_write(x, attn_output(out, lp["attn"], cfg, gate), mix, cfg)
    return ffn_close(x, lp, cfg, route, ok)


def _as_pool(pools):
    """One layer's pool tensors (None where absent), as a scan that
    writes them holds them, seen as a whole pool of that one layer (a
    free reshape) for paged_attend: (pools, layer 0)."""
    return tuple(None if a is None else a[None] for a in pools), 0


def paged_layer_body(x, lp, kp, vp, *, cfg: ModelConfig, page_table,
                     positions, mask, cos, sin, active, use_kernel: bool,
                     fresh: bool, ksp=None, vsp=None, win=None, layer=None,
                     kip=None):
    """One transformer layer against its page pool.

    Shared by paged_forward's full-stack scan, the stage-local scan of
    the pipeline serving path (parallel/pipeline.py), and the
    write-combined window path (paged_forward_window) so the three
    cannot drift. x: [B,T,D]; window off, kp/vp: [P,Kv,page,H], this
    layer's slice of the scanned pool, which the layer WRITES; ksp/vsp:
    [P,Kv*page] scale slices iff the pool is int8. Returns
    (x, kp, vp[, ksp, vsp]).

    win (kv_write_combine): (window, win_len) — the WHOLE window
    (KVWindow, leaves [L, S, Kv, W, H]), which rides the layer scan's
    carry, and the per-slot staged count. The pool is then READ-ONLY
    and comes whole too, kp/vp [L,P,Kv,page,H] (ksp/vsp [L,P,Kv*page]),
    with `layer` this layer's index in both: fresh K/V stages into the
    window's layer (stage_window_layer) instead of scattering the pool,
    and attention reads pool + window (kernel: window segment folded
    into the online softmax; dense: window inserted into the gathered
    view at absolute positions, element-wise identical to the
    window-off written view). Returns (x, window) — the pool rides
    outside the scan unchanged.

    kip (a model with an indexer): the index keys' pool, as kp is
    given; what was written comes back last.
    """
    quant = ksp is not None
    lp, q, k, v, route, sliding_window, index, mix, gate = _layer_open(
        x, lp, cfg, cos, sin)
    if win is not None:
        window, win_len = win
        B, T = k.shape[:2]
        # token t of slot b lands at window index win_len[b] + t
        window = stage_window_layer(
            window, layer, k, v, None if index is None else index[1],
            jnp.repeat(jnp.arange(B), T),
            (win_len[:, None] + jnp.arange(T)[None, :]).reshape(-1),
            (window_runs(jnp.arange(B), win_len, T, window.width),), (T,),
            use_kernel)
    else:
        kp, vp, ksp, vsp = write_paged_layer(kp, vp, page_table, k, v,
                                             positions[:, 0], active,
                                             ksp, vsp)
        if index is not None:
            kip = write_index_layer(kip, page_table, index[1],
                                    positions[:, 0], active)
    pool, layer = ((kp, vp, ksp, vsp, kip), layer) if win is not None \
        else _as_pool((kp, vp, ksp, vsp, kip))
    out = paged_attend(
        q, k, v, pool[0], pool[1], layer, cfg=cfg, page_table=page_table,
        positions=positions, mask=mask, active=active,
        use_kernel=use_kernel, fresh=fresh, ksp=pool[2], vsp=pool[3],
        win=None if win is None else (window, win_len, None),
        sliding_window=sliding_window,
        index=None if index is None else (index[0], index[2], pool[4]))
    if index is not None:
        out = out[0]
    x, _ = _layer_close(x, out, lp, cfg, mix, route, gate=gate)
    if win is not None:
        return x, window
    return (x, *(a for a in (kp, vp, ksp, vsp, kip) if a is not None))


def paged_forward(params, cfg: ModelConfig, tokens: jax.Array,
                  cache: PagedKVCache,
                  positions: Optional[jax.Array] = None,
                  active: Optional[jax.Array] = None,
                  use_kernel: bool = False,
                  fresh: bool = False,
                  last_index: Optional[jax.Array] = None):
    """Forward over [B,T] tokens against the paged cache.

    B must equal cache.num_slots (serving: one row per slot). `active`
    [B] bool masks slots with no live request: their lengths don't
    advance and their writes land on pages only they own (admission wrote
    their table), so garbage never leaks across requests. Returns
    (logits [B,T,V], updated cache).

    use_kernel: decode steps (T==1) attend through the Pallas paged-
    attention kernel — touches only each slot's live pages instead of
    gathering the full S_max view. Prefills (T>1) honor cfg.attn_impl
    ("flash" = Pallas blockwise kernel over the fresh K/V).

    last_index [B]: run the LM head only on each row's hidden state at
    that index — logits come back [B,1,V] (models.common.forward docs:
    the full-T head dominates prefill memory at LLM vocab sizes).
    """
    ssm_unsupported(cfg, LANE_WIDE)
    latent_unsupported(cfg, LANE_WIDE)
    by_kind_unsupported(cache, LANE_WIDE)
    if cfg.first_k_dense:
        gate_unsupported(cfg, LANE_WIDE)
    B, T = tokens.shape
    if positions is None:
        positions = cache.lengths[:, None] + jnp.arange(T)[None, :]
    if active is None:
        active = jnp.ones((B,), bool)

    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq) & active[:, None, None]

    def body(x, scanned):
        lp, kp, vp, ksp, vsp, kip = scanned
        out = paged_layer_body(
            x, lp, kp, vp, cfg=cfg, page_table=cache.page_table,
            positions=positions, mask=mask, cos=cos, sin=sin, active=active,
            use_kernel=use_kernel, fresh=fresh, ksp=ksp, vsp=vsp, kip=kip)
        return out[0], tuple(out[1:])

    # an absent pool tensor rides the scan as None (no leaf)
    x, new_pools = lax.scan(body, x, (layer_stack(params["layers"], cfg),
                                      *pool_leaves(cache, absent=True)))
    x = stream_fold(x, cfg)
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
    logits = final_logits(params, cfg, x)
    new_len = jnp.where(active, cache.lengths + T, cache.lengths)
    return logits, pool_leaves(cache, new_pools)._replace(lengths=new_len)


def paged_forward_window(params, cfg: ModelConfig, tokens: jax.Array,
                         cache: PagedKVCache, window: KVWindow, win_len,
                         active: Optional[jax.Array] = None,
                         use_kernel: bool = False):
    """Windowed (kv_write_combine) forward over [B,T] tokens: the pool
    is READ-ONLY, fresh K/V stages into `window` at per-slot offset
    win_len, and attention reads pool + window.

    The per-slot true length is cache.lengths (FLUSHED tokens) +
    win_len (staged), which replaces window-off paged_forward's
    positions derivation; neither cache.lengths nor win_len advances
    here — the block scan advances win_len by what it actually keeps
    (1 per live decode step; the accepted count m per spec round, which
    is what makes rollback exact: rejected entries stay past win_len,
    unattendable and never flushed). Returns (logits [B,T,V], updated
    window).

    The pool is closed over, whole, and the scan carries the layer's
    index: the paged kernel takes the pool as it lies and the index as
    a prefetched scalar, and the dense branches index layer and page in
    one gather (paged_attend). Neither scanning the read-only pools as
    xs nor a lax.dynamic_index in the body will do: each hands the
    kernel one layer's slice, and a custom call's operand is a buffer,
    so XLA copied that layer (67 MB of int8 codes at 7B, keys and again
    values) in every layer of every step. The window rides the scan's
    CARRY, whole, and is written and read by the layer's index like the
    pool (the comment above KVWindow says why not as xs/ys).
    """
    ssm_unsupported(cfg, LANE_WIDE)
    latent_unsupported(cfg, LANE_WIDE)
    by_kind_unsupported(cache, LANE_WIDE)
    if cfg.first_k_dense:
        gate_unsupported(cfg, LANE_WIDE)
    B, T = tokens.shape
    if active is None:
        active = jnp.ones((B,), bool)
    positions = (cache.lengths + win_len)[:, None] + jnp.arange(T)[None, :]
    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq) & active[:, None, None]

    def body(carry, lp):
        x, i, window = carry
        x, window = paged_layer_body(
            x, lp, cache.k_pages, cache.v_pages, cfg=cfg,
            page_table=cache.page_table,
            positions=positions, mask=mask, cos=cos, sin=sin,
            active=active, use_kernel=use_kernel, fresh=False,
            ksp=cache.k_scale_pages, vsp=cache.v_scale_pages,
            win=(window, win_len), layer=i, kip=cache.ki_pages)
        return (x, i + 1, window), None

    (x, _, window), _ = lax.scan(body, (x, 0, window),
                                 layer_stack(params["layers"], cfg))
    return final_logits(params, cfg, stream_fold(x, cfg)), window


class PackedRows(NamedTuple):
    """What every layer of one packed mixed step reads about its rows:
    N = S + P*C of them, the S decode rows first, then the P chunks
    column by column. Built once a step (packed_rows)."""
    slot: jax.Array         # [N] the slot a row belongs to
    pos: jax.Array          # [N] its absolute position
    ok: jax.Array           # [N] real: it writes its K/V
    table: Optional[jax.Array]    # [N, max_pages], window off
    widx: Optional[jax.Array]     # [N] window index (W: dropped), window on
    runs: Optional[Tuple[jax.Array, ...]]   # the same as runs of one
    # slot's consecutive entries (window_runs): the decode rows', each a
    # run of one, and with P > 0 the chunks', each a run of C
    cos: jax.Array
    sin: jax.Array
    page_table: jax.Array   # [S, max_pages] the decode rows' tables
    written: jax.Array      # [S] a slot's written length
    active: jax.Array       # [S] the slots that decode this step
    dec_mask: jax.Array
    win_len: Optional[jax.Array]  # [S] staged counts, window on
    chunk_slot: jax.Array   # [P]
    chunk_ok: jax.Array     # [P] the chunk carries something
    chunk_pos: jax.Array    # [P, C]
    chunk_mask: jax.Array
    chunk_table: jax.Array  # [P, max_pages] each chunk's OWN slot's row
    head: jax.Array         # [S] the row the LM head reads for a slot
    # a cache by kind: the decode rows' rings [S, R] and each chunk's
    # own slot's [P, R]
    ring_table: Optional[jax.Array] = None
    chunk_ring: Optional[jax.Array] = None


def packed_rows(params, cfg: ModelConfig, tokens, cache: PagedKVCache,
                chunk_tokens, chunk_slot, chunk_count, active,
                window: Optional[KVWindow] = None, win_len=None):
    """The packed step before its layers: embedded rows x [N, 1, D]
    and their PackedRows. Arguments as paged_forward_packed's."""
    S, (P, C) = tokens.shape[0], chunk_tokens.shape
    written = cache.lengths if window is None else cache.lengths + win_len
    chunk_ok = chunk_count > 0
    ccol = jnp.arange(C)[None, :]
    chunk_pos = written[chunk_slot][:, None] + ccol           # [P, C]
    slot = jnp.concatenate([jnp.arange(S), jnp.repeat(chunk_slot, C)])
    pos = jnp.concatenate([written, chunk_pos.reshape(-1)])
    ok = jnp.concatenate([active, (ccol < chunk_count[:, None]).reshape(-1)])
    tok = jnp.concatenate([tokens, chunk_tokens.reshape(-1)])
    x, cos, sin = embed_tokens(params, cfg, tok[:, None], pos[:, None])
    table = widx = runs = None
    if window is None:
        table = cache.page_table[slot]
    else:
        W = window.width
        widx = jnp.where(
            ok, win_len[slot] + jnp.concatenate(
                [jnp.zeros((S,), jnp.int32),
                 jnp.broadcast_to(ccol, (P, C)).reshape(-1)]), W)
        runs = (window_runs(jnp.arange(S), win_len, active, W),)
        if P:
            runs += (window_runs(chunk_slot, win_len[chunk_slot],
                                 jnp.clip(chunk_count, 0, C), W),)
    # a chunk's last real column stands in for its slot's own (masked)
    # decode row under the head
    hit = chunk_ok[:, None] & (chunk_slot[:, None] == jnp.arange(S)[None, :])
    last = S + jnp.arange(P) * C + jnp.clip(chunk_count - 1, 0, C - 1)
    head = jnp.where(hit.any(0), (hit * last[:, None]).sum(0), jnp.arange(S))
    return x, PackedRows(
        slot=slot, pos=pos, ok=ok, table=table, widx=widx, runs=runs,
        cos=cos, sin=sin,
        page_table=cache.page_table, written=written, active=active,
        dec_mask=make_mask(written[:, None], cache.max_seq)
        & active[:, None, None],
        win_len=win_len, chunk_slot=chunk_slot, chunk_ok=chunk_ok,
        chunk_pos=chunk_pos,
        chunk_mask=make_mask(chunk_pos, cache.max_seq)
        & chunk_ok[:, None, None],
        chunk_table=cache.page_table[chunk_slot], head=head,
        ring_table=cache.ring_table,
        chunk_ring=None if cache.ring_table is None
        else cache.ring_table[chunk_slot])


def packed_layer(x, lp, pools, window: Optional[KVWindow], rows: PackedRows,
                 cfg: ModelConfig, use_kernel: bool, layer=None,
                 looped: bool = True, kind=None):
    """One layer of the packed step. pools: (kp, vp, ksp, vsp, kip),
    scales None unless int8 and kip None unless the model has an
    indexer: with the window off this layer's slices, which it
    writes; with it on the WHOLE read-only pool, and `layer` this
    layer's index in it (paged_layer_body has the same two cases).
    window: the WHOLE window, out of the layer scan's carry, written and
    read at `layer` too, or None with the window off. Every real row's
    entry goes where the lane-wide step put it, the window at win_len
    (+ t) or the pool at the row's position, in ONE stage or scatter.
    Attention is paged_attend twice: the S decode rows as its T == 1
    case (the paged kernel, with the window segment), each chunk as its
    T == C case over its OWN slot's table row and window entries.
    Returns (x, pools, window, load): pools and window as written, and
    what the
    layer's routing asked of its experts for the step's real rows
    (_layer_close; None for a dense model). For a model with an indexer
    `load` carries four values more: sparse_paged_attend's count of
    the step's DECODE rows; for a latent-attention model one value
    more, latent_paged_attend's count (and `load` is that alone in a
    leading dense layer, which routes nothing); a latent-attention
    model with an indexer sparse_paged_attend's four in its place.
    Under cfg.experts_held the share's two values (expert_load) stay
    LAST, behind every count (before_share).
    kind (a cache by kind, window on): (the layer's index in the pool
    of its kind, whether that kind slides); `pools` is then that kind's
    pool, `layer` stays the layer's index in the window, and a sliding
    layer reads its rows through the slots' rings."""
    S, (P, C) = rows.written.shape[0], rows.chunk_pos.shape
    lp, q, k, v, route, sliding_window, index, mix, gate = _layer_open(
        x, lp, cfg, rows.cos, rows.sin)
    dec_win = chunk_win = None
    if window is not None:
        window = stage_window_layer(
            window, layer, k, v, None if index is None else index[1],
            rows.slot, rows.widx, rows.runs, (1, C)[:len(rows.runs)],
            use_kernel, looped)
        dec_win = (window, rows.win_len, None)
        chunk_win = (window, rows.win_len[rows.chunk_slot], rows.chunk_slot)
    else:
        kip = pools[4]
        if index is not None:
            kip = write_index_layer(kip, rows.table, index[1], rows.pos,
                                    rows.ok)
        pools = (*write_paged_layer(pools[0], pools[1], rows.table, k, v,
                                    rows.pos, rows.ok, pools[2], pools[3]),
                 kip)
    (kp, vp, ksp, vsp, kip), layer = (pools, layer) if window is not None \
        else _as_pool(pools)
    ring, slides = None, False
    if kind is not None:
        ring, layer, slides = (layer, kind[1]), kind[0], kind[1]
    attend_rows = partial(paged_attend, kp=kp, vp=vp, layer=layer, cfg=cfg,
                          use_kernel=use_kernel, fresh=False,
                          ksp=ksp, vsp=vsp, sliding_window=sliding_window,
                          ring=ring)

    def rows_index(cut):
        """paged_attend's `index` for one group of rows."""
        return None if index is None else (cut(index[0]), cut(index[2]), kip)

    out = attend_rows(q[:S], k[:S], None if v is None else v[:S],
                      page_table=rows.ring_table if slides
                      else rows.page_table,
                      positions=rows.written[:, None], mask=rows.dec_mask,
                      active=rows.active, win=dec_win,
                      index=rows_index(lambda a: a[:S]))
    count = None
    counted = index is not None or cfg.is_latent
    if counted:
        out, count = out
    if P:
        def chunks(a):
            """Rows S.. of a packed [N, 1, ...] array as [P, C, ...]."""
            return None if a is None else a[S:].reshape(P, C, *a.shape[2:])

        out_c = attend_rows(chunks(q), chunks(k), chunks(v),
                            page_table=rows.chunk_ring if slides
                            else rows.chunk_table,
                            positions=rows.chunk_pos, mask=rows.chunk_mask,
                            active=rows.chunk_ok, win=chunk_win,
                            index=rows_index(chunks))
        if counted:
            out_c = out_c[0]
        out = jnp.concatenate(
            [out, out_c.reshape(P * C, 1, *out_c.shape[2:])])
    x, load = _layer_close(x, out, lp, cfg, mix, route, rows.ok[:, None],
                           gate)
    if count is not None:
        if load is None and not cfg.is_latent:
            load = jnp.zeros((3,), jnp.float32)
        load = count if load is None else before_share(load, count, cfg)
    return x, pools, window, load


def _packed_runs(params, cfg: ModelConfig, x, rows: PackedRows,
                 cache: PagedKVCache, window: Optional[KVWindow],
                 state: Optional[SSMState], use_kernel: bool):
    """The layers of a packed step for a model whose layers are of
    unlike SHAPES, in the published order: mixers of two kinds
    (cfg.layer_types: one recurrent kind beside attention) or
    feed-forwards of two kinds (cfg.first_k_dense).
    A cache that keeps the sliding layers' rows apart
    (PagedKVCache.by_kind) runs every model so: a run then also ends
    where sliding layers meet full ones, and `load` ends (before a
    share's two) in the rows the sliding layers' decode rows read and
    what they would have read with no window.
    Each run of one kind (models.common.layer_runs) is one scan that
    rides the layers' indices, into params["layers"] (what every layer
    has), into its mixer's own stack where there is one (params["mamba"]
    or params["gdn"], params["attn"]) and into its feed-forward's
    (ffn_run). A recurrent run (Mamba-2 or Gated DeltaNet) carries the
    recurrent state (ssm_state.advance_packed by the layer's kind); an
    attention run is packed_layer as every other model runs it, over the
    pool's layer a (the pool holds attention layers only): window on,
    the read-only pool whole and the window, whole, in the carry of
    every run's scan, each run starting from its first layer's index
    among the attention layers; window off, the run's pool slices as
    xs. Returns (x, window or cache as written,
    state, load): load the mean of expert_load over the layers that
    route; a latent-attention model's ends in the SUM over the layers
    of latent_paged_attend's count."""
    pools = pool_leaves(cache, absent=True)
    written, loads = [], []

    def recurrent(carry, idx):
        x, st = carry
        l, m = idx
        x, st, load = advance_packed(
            x, layer_at(params["layers"], l, cfg),
            layer_at(params[RECURRENT_STACKS[cfg.recurrent_kind]], m, cfg),
            st, m, rows, cfg, use_kernel)
        return (x, st), load

    def attention(ffn, held, looped, slides, carry, scanned):
        x, win = carry
        (l, a, *kth), *mine = scanned
        lp = run_layer_at(params, ffn, l, cfg, held)
        if "attn" in params:
            lp = {**lp, "attn": layer_at(params["attn"], a, cfg)}
        if win is None:
            x, new, _, load = packed_layer(x, lp, mine, None, rows, cfg,
                                           use_kernel)
            return (x, None), (new, load)
        # a cache by kind (kth): the run's layers are of ONE window
        # kind, read from that kind's pool at their index in it
        x, _, win, load = packed_layer(
            x, lp, ring_pools if slides else pools, win, rows, cfg,
            use_kernel, layer=a, looped=looped,
            kind=(kth[0], slides) if kth else None)
        return (x, win), (None, load)

    ring_pools = (cache.k_ring, cache.v_ring, None, None, None)
    for kind, first, n, at, *of_kind in layer_runs(cfg, cache.by_kind):
        idx = (first + jnp.arange(n), at + jnp.arange(n),
               *((of_kind[0] + jnp.arange(n),) if of_kind else ()))
        if kind != "attention":
            (x, state), load = lax.scan(recurrent, (x, state), idx)
        else:
            mine = () if window is not None else (
                None if a is None else a[at:at + n] for a in pools)
            ffn, held = ffn_run(params, first, cfg), None
            if ffn is not None:
                # a run's experts stay whole where its steps take the
                # Mosaic call (experts_in_place), its layer by index
                stack, held = experts_in_place(ffn[0], rows.ok.shape[0], cfg,
                                               use_kernel)
                ffn = (stack, ffn[1])
            (x, window), (new, load) = lax.scan(
                partial(attention, ffn, held, n > 1,
                        bool(of_kind and of_kind[1])),
                (x, window), (idx, *mine))
            written.append(new)
        loads.append(load)
    kv = window
    if window is None:
        # one run's slices as they are; several runs' joined in order
        kv = pool_leaves(cache, pools if not written else written[0]
                         if len(written) == 1 else tuple(
                             None if a[0] is None else jnp.concatenate(a)
                             for a in zip(*written)))
    if cfg.is_latent:
        # a dense layer's load is its read's count alone (one value, an
        # indexer's four); an expert layer's holds it behind the
        # experts' three and before the share's two (before_share). The
        # experts' and the share's are the mean over the layers that
        # route; the count is the SUM over the layers, an indexer's four
        # the mean, as every model with an indexer gives them
        n = 4 if cfg.has_indexer else 1
        routed = [l for l in loads if l.shape[1] > n]
        parts = [jnp.concatenate([l[:, :3] for l in routed]).mean(axis=0)]
        if cfg.has_indexer:
            parts.append(jnp.concatenate(
                [l if l.shape[1] == n else l[:, 3:3 + n]
                 for l in loads]).mean(axis=0))
        else:
            parts.append(sum(l[:, -1 if l.shape[1] == n else 3].sum()
                             for l in loads)[None])
        if cfg.experts_held:
            parts.append(jnp.concatenate(
                [l[:, 3 + n:] for l in routed]).mean(axis=0))
        return x, kv, state, jnp.concatenate(parts)
    # a dense model's layers route nothing: three zeros hold the
    # experts' places before what a family adds (_mixed_rows does so)
    routed = [l for l in loads if l is not None]
    load = jnp.concatenate(routed).mean(axis=0) if routed \
        else jnp.zeros((3,), jnp.float32)
    if cache.by_kind:
        # what the sliding layers' decode rows READ this step and what
        # they would have read with no window, summed over those layers
        # (the tick record's swa_rows_read / swa_rows_whole): a live
        # row at position p reads min(p + 1, window) rows a layer
        n = jnp.where(rows.active, rows.written + 1, 0)
        ls = cache.k_ring.shape[0]
        swa = jnp.stack([jnp.sum(jnp.minimum(n, cfg.sliding_window)),
                         jnp.sum(n)]) * ls
        load = before_share(load, swa.astype(jnp.float32), cfg)
    return x, kv, state, load


_POOL_LEAVES = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages",
                "ki_pages")
_WINDOW_LEAVES = ("k", "v", "k_scale", "v_scale", "ki")


def _leaves(holder, names, new, absent: bool):
    there = [n for n in names if getattr(holder, n) is not None]
    if new is not None:
        # all the places (None where absent), or the tensors there are
        keys = names if len(new) == len(names) else there
        return holder._replace(**dict(zip(keys, new, strict=True)))
    return tuple(getattr(holder, n) for n in (names if absent else there))


def pool_leaves(cache: PagedKVCache, pools=None, absent: bool = False):
    """The cache's pool tensors as a list: keys and values, their
    scales iff int8, the index keys iff the model has an indexer; what
    rides a layer scan, what a flush writes. absent: all five places,
    None where the cache has no such tensor (a scan takes None as no
    leaf). Given `pools`, the tensors that are there in that order, the
    cache with them put back instead."""
    return _leaves(cache, _POOL_LEAVES, pools, absent)


def window_leaves(window: KVWindow, staged=None, absent: bool = False):
    """pool_leaves for the window: its tensors in the pool's order."""
    return _leaves(window, _WINDOW_LEAVES, staged, absent)


def before_share(load, more, cfg: ModelConfig):
    """A step's `load` with the values `more` at its end, or, under
    cfg.experts_held, before its last two: the share's counts
    (models.common.expert_load: the assignments that fell on a held
    expert, and all) stay LAST whatever a family adds, where the block
    scan and the scheduler find them."""
    if not cfg.experts_held:
        return jnp.concatenate([load, more])
    return jnp.concatenate([load[:-2], more, load[-2:]])


def _mixed_rows(load, rows: PackedRows, cfg: ModelConfig):
    """A packed step's `load` with, for a model of n residual streams
    (cfg.hc_mult), ONE value more at its end: the positions whose
    streams this step mixed (every real row: a live decode row, a
    chunk's real columns; filler and idle rows none), what the tick
    record's `hc_rows` sums. A dense such model's load is three zeros
    before it (no routing). Every other model's load as it is."""
    if not cfg.hc_mult:
        return load
    mixed = jnp.sum(rows.ok).astype(jnp.float32)[None]
    if load is None:
        return jnp.concatenate([jnp.zeros((3,), jnp.float32), mixed])
    return before_share(load, mixed, cfg)


def paged_forward_packed(params, cfg: ModelConfig, tokens: jax.Array,
                         cache: PagedKVCache, chunk_tokens: jax.Array,
                         chunk_slot: jax.Array, chunk_count: jax.Array,
                         active: jax.Array,
                         window: Optional[KVWindow] = None,
                         win_len: Optional[jax.Array] = None,
                         use_kernel: bool = False,
                         state: Optional[SSMState] = None):
    """One PACKED mixed step: S decode rows of one token beside P
    prefill chunks of C tokens, S + P*C rows through every projection,
    the feed-forward and (on a mesh) the all-reduces, where the
    lane-wide step ran S*C.

    tokens [S]: each slot's chain token; `active` [S] marks the slots
    that decode this step (a slot in prefill phase is NOT among them).
    chunk_tokens [P, C]: the next C prompt tokens of slot chunk_slot[p],
    of which the first chunk_count[p] are real (0: chunk p carries
    nothing this step, and writes and yields nothing). A row sits at
    its slot's written length (cache.lengths, plus win_len when the
    window is on), a chunk's column t at that plus t.

    Keys, values and attention: packed_layer. Filler columns, idle
    chunks and inactive slots write nothing; a chunk's dense view is
    [P, S_max], not the whole pool, and flash takes it where
    cfg.attn_impl and the window allow.

    Returns (logits [S, V] float32, pools-or-window, load): the LM head runs
    on S rows, a decoding slot's own and, for a slot with a chunk, the
    chunk's last real column (its first token, if the prompt ends
    there). Window off the second value is the cache with its pools
    written and its lengths as they were; window on, the window.
    Neither length advances here: the block scan knows what it keeps.
    `load` f32 [3] is models.common.expert_load of the step's real
    rows, the mean over the layers (None for a dense model): distinct
    experts touched, rows of the fullest expert, mean rows an expert.
    A model with an indexer adds sparse_paged_attend's four: decode
    rows, the positions they could attend, the positions they attended,
    the rows the read moved; a latent-attention model one: the cached
    rows its decode rows read, summed over the layers
    (latent_paged_attend).
    (Under pipeline stages: parallel/pipeline.py paged_pipeline_packed,
    the same pieces over stage-local layers.)

    state (a model with recurrent layers: cache/ssm_state.py): every
    slot's recurrent state, which the step's real rows advance. The
    layers then run as scans over runs of one kind (_packed_runs) and
    the return gains the state as a fourth value; `load` gains two:
    the positions pushed through a recurrence this step (decode rows
    and real chunk columns) and the slots that started from zero.
    A model of n residual streams (cfg.hc_mult): x is [n, N, 1, D]
    through the layers, and `load` ends in the positions mixed
    (_mixed_rows).
    """
    x, rows = packed_rows(params, cfg, tokens, cache, chunk_tokens,
                          chunk_slot, chunk_count, active, window, win_len)
    if cfg.has_ssm or cfg.first_k_dense or cache.by_kind:
        x, kv, state, load = _packed_runs(params, cfg, x, rows, cache,
                                          window, state, use_kernel)
        logits = final_logits(params, cfg,
                              stream_fold(x, cfg)[rows.head])[:, 0]
        if not cfg.has_ssm:
            return logits, kv, _mixed_rows(load, rows, cfg)
        ssm = jnp.stack([jnp.sum(rows.ok), jnp.sum(
            rows.chunk_ok & (rows.chunk_pos[:, 0] == 0))])
        load = before_share(load, ssm.astype(jnp.float32), cfg)
        return logits, kv, load, state
    # the experts' codes ride no scan where the step takes the Mosaic
    # call: they stay whole, read at the layer's index (experts_in_place)
    layers, held = experts_in_place(layer_stack(params["layers"], cfg),
                                    rows.ok.shape[0], cfg, use_kernel)
    # an absent pool or window tensor rides the scan as None (no leaf)
    if window is None:
        def body(x, scanned):
            i, lp, *pools = scanned
            x, pools, _, load = packed_layer(
                x, layer_experts(lp, held, i), pools, None, rows, cfg,
                use_kernel)
            return x, (pools, load)

        x, (pools, load) = lax.scan(
            body, x, (held and jnp.arange(len(jax.tree.leaves(layers)[0])),
                      layers, *pool_leaves(cache, absent=True)))
        state = pool_leaves(cache, pools)
    else:
        # the pool is read-only and goes in whole beside the layer's
        # index, as in paged_forward_window; the window rides the carry,
        # whole, written and read by the same index
        def body(carry, lp):
            x, i, window = carry
            x, _, window, load = packed_layer(
                x, layer_experts(lp, held, i),
                pool_leaves(cache, absent=True), window, rows, cfg,
                use_kernel, layer=i)
            return (x, i + 1, window), load

        (x, _, state), load = lax.scan(body, (x, 0, window), layers)
    if load is not None:
        load = load.mean(axis=0)
    return (final_logits(params, cfg, stream_fold(x, cfg)[rows.head])[:, 0],
            state, _mixed_rows(load, rows, cfg))
