"""Transformer partitioner: param/cache/activation PartitionSpecs.

Realizes the reference's planned "Model Partitioning" layer
(/root/reference/CLAUDE.md:21 — "Algorithms to intelligently divide
transformer layers/attention heads") the TPU way: instead of manually
slicing tensors and issuing NCCL calls, we attach `PartitionSpec`s to every
leaf of the param/cache pytrees and let GSPMD lower the einsums to sharded
matmuls with `all-reduce`/`all-gather` placed at the Megatron-canonical
points:

* attention: wq/wk/wv column-parallel (heads sharded over `tensor`), wo
  row-parallel -> one all-reduce per attention block;
* MLP: w_up/w_gate column-parallel, w_down row-parallel -> one all-reduce
  per MLP block;
* MoE experts sharded over `expert` (dispatch handled in parallel/expert.py);
* embedding vocab-sharded; lm_head column-parallel over vocab;
* KV cache: batch over `data`, kv-heads over `tensor`.

Sharding is *advisory for layout, mandatory for memory*: a spec only ever
shards a dim that divides evenly by the mesh axis (else that dim is
replicated), so any (cfg, mesh) combination is valid. Tests verify parity
TP=1 vs TP=8 and assert the expected collectives appear in the compiled
HLO (SURVEY.md §7 stage 2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from butterfly_tpu.core.config import ModelConfig
from butterfly_tpu.models.common import KVCache

Specs = Dict[str, Any]


def _div(n: int, mesh: Mesh, axis: str) -> Optional[str]:
    """Return `axis` if dim of size n shards evenly over it, else None."""
    return axis if n % mesh.shape[axis] == 0 and mesh.shape[axis] > 1 else None


def _div_multi(n: int, mesh: Mesh, *axes: str):
    """Largest prefix-combination of active `axes` that divides n.

    Tries the full product first, then drops leading axes — e.g.
    ("stage", "tensor") falls back to tensor-only when n isn't divisible
    by stage*tensor. Returns an axis tuple / name / None (P dim entry)."""
    for i in range(len(axes)):
        active = [a for a in axes[i:] if mesh.shape[a] > 1]
        size = 1
        for a in active:
            size *= mesh.shape[a]
        if active and n % size == 0:
            return tuple(active) if len(active) > 1 else active[0]
    return None


def param_specs(cfg: ModelConfig, mesh: Mesh) -> Specs:
    """PartitionSpec pytree mirroring models.common.init_params exactly.

    Layer-stacked leaves have a leading L dim; when pipeline parallelism is
    active (mesh axis `stage` > 1) that dim is sharded over `stage` so each
    stage group holds only its own layers' weights.
    """
    from butterfly_tpu.models.common import latent_unsupported
    latent_unsupported(cfg, "a device mesh (parallel/partition.py has no "
                            "layout for the latent projections)")
    D, Nq, Kv, F, V = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                       cfg.intermediate_size, cfg.vocab_size)
    tp = lambda n: _div(n, mesh, "tensor")  # noqa: E731
    L = _div(cfg.num_layers, mesh, "stage")

    layers: Specs = {
        "ln1": {"scale": P(L, None)},
        "ln2": {"scale": P(L, None)},
        "attn": {
            "wq": P(L, None, tp(Nq), None),   # column-parallel (heads)
            "wk": P(L, None, tp(Kv), None),
            "wv": P(L, None, tp(Kv), None),
            "wo": P(L, tp(Nq), None, None),   # row-parallel -> all-reduce
        },
    }
    if cfg.qk_norm:   # one weight [H] for every head: replicated
        layers["attn"]["q_norm"] = {"scale": P(L, None)}
        layers["attn"]["k_norm"] = {"scale": P(L, None)}
    if cfg.has_indexer:
        # the indexer is a thousandth of a layer and its selection is one
        # a token for all heads: every chip holds it whole
        layers["index"] = {
            "w_qi": P(L, None, None, None), "w_ki": P(L, None, None),
            "w_w": P(L, None, None),
            "k_norm": {"scale": P(L, None), "bias": P(L, None)},
        }
    if cfg.use_bias:
        layers["ln1"]["bias"] = P(L, None)
        layers["ln2"]["bias"] = P(L, None)
        layers["attn"].update(
            bq=P(L, tp(Nq), None), bk=P(L, tp(Kv), None),
            bv=P(L, tp(Kv), None), bo=P(L, None),
        )
    if cfg.routed:
        E = cfg.num_experts
        ep = _div(E, mesh, "expert")
        layers["moe"] = {
            "router": P(L, None, None),
            "w_gate": P(L, ep, None, tp(F)),
            "w_up": P(L, ep, None, tp(F)),
            "w_down": P(L, ep, tp(F), None),
        }
    elif cfg.arch == "gpt2":
        layers["mlp"] = {
            "w_up": P(L, None, tp(F)), "b_up": P(L, tp(F)),
            "w_down": P(L, tp(F), None), "b_down": P(L, None),
        }
    else:
        layers["mlp"] = {
            "w_gate": P(L, None, tp(F)),
            "w_up": P(L, None, tp(F)),
            "w_down": P(L, tp(F), None),
        }

    specs: Specs = {
        "embed": {"tok": P(tp(V), None)},
        "layers": layers,
        "final_norm": {"scale": P(None)},
    }
    if cfg.pos_embedding == "learned":
        specs["embed"]["pos"] = P(None, None)
    if cfg.arch == "gpt2":
        specs["final_norm"]["bias"] = P(None)
    if not cfg.tie_embeddings:
        # Vocab over stage AND tensor: a pipeline mesh would otherwise
        # replicate the D*V head on every stage (VERDICT r2 weak item 4).
        # The matmul contracts the replicated D dim, so sharding only
        # splits the output — no extra all-reduce; logits are produced
        # vocab-sharded and consumers gather the (tiny) last-token slice.
        specs["lm_head"] = P(None, _div_multi(V, mesh, "stage", "tensor"))
    return specs


def cache_specs(cfg: ModelConfig, mesh: Mesh, quant: bool = False) -> KVCache:
    """Specs for the KVCache pytree: layers x stage (mirrors the param
    layout so each pipeline stage holds only its own layers' cache),
    batch x data, kv-heads x tensor. Float caches are [L,B,S,Kv,H];
    int8 caches are [L,B,Kv,S,H] + scale leaves [L,B,Kv,S] (see
    models.common.KVCache for why the dim orders differ)."""
    lspec = _div(cfg.num_layers, mesh, "stage")
    dspec = _div_any(mesh, "data")
    tspec = _div(cfg.num_kv_heads, mesh, "tensor")
    if quant:
        kv = P(lspec, dspec, tspec, None, None)
        sc = P(lspec, dspec, tspec, None)
    else:
        kv = P(lspec, dspec, None, tspec, None)
        sc = None
    ki = P(lspec, dspec, None, None) if cfg.has_indexer else None
    return KVCache(k=kv, v=kv, length=P(dspec), k_scale=sc, v_scale=sc,
                   ki=ki)


def _div_any(mesh: Mesh, axis: str) -> Optional[str]:
    """Axis name if it is active (>1); batch dims are chosen divisible."""
    return axis if mesh.shape[axis] > 1 else None


def paged_cache_specs(cfg: ModelConfig, mesh: Mesh, num_slots: int,
                      quant: bool = False):
    """Specs for the PagedKVCache pytree (serving under a mesh).

    Pool k/v_pages [L,P,Kv,page,H]: layers over `stage` (each pipeline
    stage owns only its local layers' pages, mirroring param_specs),
    kv-heads over `tensor` (matching the Megatron column-parallel wk/wv
    so paged writes stay local to the TP shard). The page-id dim P stays
    replicated: page ownership is a host-allocator concept and any slot
    may reference any page, so sharding P would turn every gather into a
    cross-`data` collective. Slot-indexed leaves (page_table [S,maxp],
    lengths [S]) shard slots over `data` when divisible — the decode step
    then runs data-parallel over slots. int8 pools add scale leaves
    [L,P,Kv*page] whose flat dim shards over `tensor` iff Kv does (a
    tensor chunk of the kv-major flat dim is exactly one kv-group's
    scales — see cache/paged.py layout notes). A token-major pool
    [L,P,1,page,Kv*H] (cache/paged.py pool_row: a model with an
    indexer) shards its MINOR dim over `tensor`: a chip's KV heads lie
    contiguous in a token's row, so its chunk is the same heads.
    """
    from butterfly_tpu.cache.paged import PagedKVCache
    dslots = _div(num_slots, mesh, "data")
    lspec = _div(cfg.num_layers, mesh, "stage")
    tspec = _div(cfg.num_kv_heads, mesh, "tensor")
    kv = P(lspec, None, *_kv_row_spec(cfg, mesh))
    sc = P(lspec, None, tspec) if quant else None
    # the index keys have ONE head a token: every chip holds them whole
    ki = P(lspec, None, None, None, None) if cfg.has_indexer else None
    return PagedKVCache(k_pages=kv, v_pages=kv,
                        page_table=P(dslots, None), lengths=P(dslots),
                        k_scale_pages=sc, v_scale_pages=sc, ki_pages=ki)


def _kv_row_spec(cfg: ModelConfig, mesh: Mesh) -> Tuple:
    """The (heads, page or window, width) dims of a paged pool or its
    window: KV heads over `tensor`, in the dim that holds them (dim 2 of
    a head-major page, the minor dim of a token-major one)."""
    from butterfly_tpu.cache.paged import pool_layout
    tspec = _div(cfg.num_kv_heads, mesh, "tensor")
    if pool_layout(cfg) == "head":
        return tspec, None, None
    return None, None, tspec


def kv_window_specs(cfg: ModelConfig, mesh: Mesh, num_slots: int,
                    quant: bool = False):
    """Specs for the write-combined KV window (cache/paged.py KVWindow,
    [L, S, Kv, W, H]): slots over `data` with the block table / q rows,
    kv-heads over `tensor` with the pools — so staging, the kernel's
    window segment, and the flush scatter all stay local to the shard
    that owns the matching pool bytes. L stays replicated (the window
    only exists on the non-pipeline serving path; stage > 1 falls back
    to per-token writes). A token-major window [L, S, 1, W, Kv*H]
    shards its minor dim, as its pool does."""
    from butterfly_tpu.cache.paged import KVWindow
    dslots = _div(num_slots, mesh, "data")
    tspec = _div(cfg.num_kv_heads, mesh, "tensor")
    kv = P(None, dslots, *_kv_row_spec(cfg, mesh))
    # an int8 window's scales [L, S, W/ws, Kv*ws]: a `tensor` chunk of
    # a step's flat kv-major row is its heads' scales, as a page's is
    sc = P(None, dslots, None, tspec) if quant else None
    ki = P(None, dslots, None, None, None) if cfg.has_indexer else None
    return KVWindow(k=kv, v=kv, k_scale=sc, v_scale=sc, ki=ki)


def warm_prefix_specs(d: Optional[str], t: Optional[str],
                      quant: bool) -> Tuple:
    """In_specs for the warm-prefix flash kernel's cached-context
    operands (ops/flash_attention.py warm-prefix prefill, ISSUE 13), in
    call order: (prefix_k, prefix_v, prefix_len[, k_scale, v_scale]).

    The prefix is the cache in the representation attend() consumes —
    float view [B, S, Kv, H], or int8 codes [B, Kv, S, H] + per-vector
    scales [B, Kv, S] — so batch/slots shard over `data` with the q
    rows and kv heads over `tensor` with the pools, exactly the axes
    paged_cache_specs/cache_specs give the backing cache. `d`/`t` are
    the axis names shardable_axes resolved for this call site (None =
    replicated), not a mesh: the kernel wrapper picks them per dispatch.
    """
    if quant:
        code = P(d, t, None, None)
        return (code, code, P(d), P(d, t, None), P(d, t, None))
    view = P(d, None, t, None)
    return (view, view, P(d))


def activation_spec(mesh: Mesh, seq_sharded: bool = False) -> P:
    """[B,T,D] activations: batch over data, optionally seq over `seq`."""
    return P(_div_any(mesh, "data"), "seq" if seq_sharded and
             mesh.shape["seq"] > 1 else None, None)


def logits_spec(cfg: ModelConfig, mesh: Mesh) -> P:
    return P(_div_any(mesh, "data"), None, _div(cfg.vocab_size, mesh, "tensor"))


# ---------------------------------------------------------------------------
# Application helpers
# ---------------------------------------------------------------------------

def to_shardings(specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params, cfg: ModelConfig, mesh: Mesh):
    """device_put every param leaf to its partitioned layout."""
    return jax.device_put(params, to_shardings(param_specs(cfg, mesh), mesh))


def shard_cache(cache: KVCache, cfg: ModelConfig, mesh: Mesh) -> KVCache:
    return jax.device_put(cache, to_shardings(
        cache_specs(cfg, mesh, quant=cache.quantized), mesh))


# ---------------------------------------------------------------------------
# HLO inspection (test/debug aid: verify collective placement, SURVEY.md §7)
# ---------------------------------------------------------------------------

def compiled_hlo(fn, *args, mesh: Optional[Mesh] = None, **jit_kw) -> str:
    """Lower+compile fn under `mesh` and return optimized HLO text."""
    jfn = jax.jit(fn, **jit_kw)
    if mesh is not None:
        # set_mesh also installs the abstract mesh that mesh-aware call
        # sites (kernel wrappers, EP a2a dispatch) consult during
        # tracing — matching how the engines actually run.
        with jax.set_mesh(mesh):
            lowered = jfn.lower(*args)
    else:
        lowered = jfn.lower(*args)
    return lowered.compile().as_text()


def count_collectives(hlo: str) -> Dict[str, int]:
    """Count collective ops in optimized HLO text, keyed by op name."""
    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
    counts = {op: 0 for op in ops}
    for line in hlo.splitlines():
        s = line.lstrip()
        # count op *instances*: lines like `%all-reduce.3 = ...` or
        # `ROOT %all-gather ...`, not parameter references. Async pairs
        # (`-start`/`-done`) are one logical collective: skip `-done`.
        if "=" not in s:
            continue
        lhs = s.split("=", 1)[0]
        if "-done" in lhs:
            continue
        for op in ops:
            if op in lhs:
                counts[op] += 1
    return counts
