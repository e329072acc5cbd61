"""Pipeline parallelism: GPipe microbatch schedule over the `stage` mesh axis.

TPU-native realization of the reference's planned pipeline-stage send/recv
(/root/reference/CLAUDE.md:19-22 names the layers; no implementation exists
— SURVEY.md §0). Instead of point-to-point NCCL send/recv between stage
processes, the whole pipeline is ONE SPMD program:

* layer-stacked params/cache keep their leading L dim; `shard_map` manual
  over `stage` gives each stage its local [L/S, ...] slice;
* stage handoff is `lax.ppermute` (XLA collective-permute — on TPU this
  rides neighbor ICI links, the canonical pipeline transport);
* the microbatch schedule is a `lax.scan` over M + S - 1 ticks (GPipe):
  tick t has stage s working on microbatch m = t - s; invalid (bubble)
  ticks compute on garbage and are masked out of all writes;
* `tensor`/`data` axes stay under GSPMD auto partitioning *inside* the
  body (shard_map axis_names={'stage'}), so TPxPP composes without manual
  collectives: the per-stage einsums still get their Megatron all-reduces
  from the partitioner's specs.

Bubble fraction is (S-1)/(M+S-1); pick num_microbatches >= 4*S for decode
throughput parity with the north star (BASELINE.json configs[2]).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from butterfly_tpu.core.config import ModelConfig
from butterfly_tpu.models.common import (
    KVCache, Params, embed_tokens, final_logits, make_mask, scan_layers,
    indexer_unsupported, ssm_unsupported, streams_unsupported,
    uniform_layers_only)


def pipeline_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     cache: KVCache, mesh: Mesh,
                     num_microbatches: Optional[int] = None,
                     positions: Optional[jax.Array] = None,
                     fresh: bool = False,
                     virtual_stages: int = 1
                     ) -> Tuple[jax.Array, KVCache]:
    """Full forward with the layer stack pipelined over `stage`.

    Embedding and LM head run under plain GSPMD (they are outside the
    stage loop; on a real pod they live with stage 0 / stage S-1 layer
    weights — replicated here, cheap relative to the stack). Requires
    cfg.num_layers % S == 0 and batch % num_microbatches == 0.

    virtual_stages V > 1 selects the INTERLEAVED schedule (SURVEY.md §7
    stage 3 "interleaved 1F1B-style decode"): each device owns V
    round-robin layer chunks and activations make V trips around a
    wrapping ppermute ring, cutting the bubble from (S-1)/(M+S-1) to
    (S-1)/(V*M+S-1) — the decode-latency win when M can't be large.
    Params/cache must then be in interleaved layer order (one-time
    permutation via `interleave_layers`), and M >= S so wrapped
    activations arrive before they're consumed.
    """
    S = mesh.shape["stage"]
    B, T = tokens.shape
    if positions is None:
        positions = cache.length[:, None] + jnp.arange(T)[None, :]
    if S == 1:
        from butterfly_tpu.models.common import forward
        return forward(params, cfg, tokens, cache, positions, fresh=fresh)
    uniform_layers_only(cfg, "pipeline parallelism")
    indexer_unsupported(cfg, "pipeline parallelism")
    ssm_unsupported(cfg, "pipeline parallelism")
    streams_unsupported(cfg, "pipeline parallelism")

    M = num_microbatches or _default_microbatches(B, S)
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    if cfg.num_layers % S != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible by {S} stages")
    V = virtual_stages
    if V > 1:
        if cfg.num_layers % (S * V) != 0:
            raise ValueError(f"{cfg.num_layers} layers not divisible by "
                             f"{S} stages x {V} virtual chunks")
        if M < S:
            raise ValueError(
                f"interleaved schedule needs microbatches >= stages "
                f"({M} < {S}): a wrapped activation produced at tick "
                f"t reaches stage 0 at t+1 but is consumed at t+M-S+1")

    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq)

    quant = cache.quantized
    stage_ops = (cache.k, cache.v)
    if quant:  # scale leaves stage-shard their L dim like the code leaves
        stage_ops += (cache.k_scale, cache.v_scale)
    if V > 1:
        body = partial(_interleaved_body, cfg=cfg, S=S, M=M, V=V,
                       fresh=fresh, quant=quant)
    else:
        body = partial(_pipeline_body, cfg=cfg, S=S, M=M, fresh=fresh,
                       quant=quant)
    y, new_cache = _run_gpipe(body, mesh, params["layers"], stage_ops,
                              (x, positions, mask, cos, sin), S, M, x)
    logits = final_logits(params, cfg, y)
    return logits, KVCache(new_cache[0], new_cache[1], cache.length + T,
                           *new_cache[2:])


def interleave_layers(tree, num_layers: int, S: int, V: int,
                      inverse: bool = False):
    """Permute stacked-L leaves into (or back out of) interleaved order.

    Interleaved pipeline layout: stage s's contiguous [L/S] block holds
    the round-robin chunks v*S + s for v in 0..V-1, so shard_map's
    P('stage') on the L dim gives each stage exactly its interleaved
    chunks. Apply ONCE at weight-load/cache-init — not per step.
    Leaves whose leading dim != num_layers are passed through.
    """
    import numpy as np
    Lc = num_layers // (S * V)
    order = np.asarray([(v * S + s) * Lc + i
                        for s in range(S) for v in range(V)
                        for i in range(Lc)])
    if inverse:
        inv = np.empty_like(order)
        inv[order] = np.arange(num_layers)
        order = inv

    def perm(a):
        if hasattr(a, "shape") and a.ndim >= 1 and a.shape[0] == num_layers:
            return jnp.take(a, jnp.asarray(order), axis=0)
        return a

    return jax.tree.map(perm, tree)


def _run_gpipe(body, mesh: Mesh, layers, stage_ops, rep_ops, S: int, M: int,
               x: jax.Array):
    """shard_map a GPipe body and slice the last stage's result block.

    Shared scaffolding for the contiguous and paged pipelines. Manual
    over `stage` only: layer-stacked leaves and `stage_ops` (the cache
    pytree leaves) split their leading L dim; `rep_ops` (activations,
    masks, tables) are replicated over stage; tensor/data stay auto
    (GSPMD) inside. The body's microbatch results come back stage-
    STACKED ([S*M, mb, ...], only the last stage's block meaningful)
    rather than psum-replicated: slicing that block moves ONE [B,T,D]
    activation off the last stage instead of all-reducing S zero-padded
    copies (VERDICT r2 weak item 4).
    """
    layer_in = jax.tree.map(lambda _: P("stage"), layers)
    pipe = jax.shard_map(
        body, mesh=mesh,
        in_specs=(layer_in, *([P("stage")] * len(stage_ops)),
                  *([P()] * len(rep_ops))),
        out_specs=(P("stage"), *([P("stage")] * len(stage_ops))),
        axis_names={"stage"}, check_vma=False)
    outs, *new_stage = pipe(layers, *stage_ops, *rep_ops)
    return outs[(S - 1) * M:].reshape(x.shape), tuple(new_stage)


def paged_pipeline_forward(params: Params, cfg: ModelConfig,
                           tokens: jax.Array, cache,
                           positions: Optional[jax.Array] = None,
                           active: Optional[jax.Array] = None,
                           use_kernel: bool = False, fresh: bool = False,
                           last_index: Optional[jax.Array] = None,
                           *, mesh: Mesh,
                           num_microbatches: Optional[int] = None):
    """paged_forward pipelined over `stage` (VERDICT r2 item 4).

    `last_index` is accepted for signature parity with paged_forward but
    ignored — the GPipe schedule emits full-T logits per microbatch and
    the caller gathers.

    Same contract as cache.paged.paged_forward — [B,T] tokens against the
    shared page pool — but the layer stack and the pool's L dim are stage-
    sharded and microbatches of slots flow through the GPipe schedule.
    Block tables/lengths stay replicated over stage (page ownership is a
    host concept); each stage scatters/gathers only its local layers'
    pages. The Pallas kernels still engage inside the stage-manual region
    (their wrappers shard_map over the still-Auto data/tensor axes).
    """
    from butterfly_tpu.cache.paged import PagedKVCache, paged_forward
    from butterfly_tpu.models.common import (
        embed_tokens, final_logits, make_mask)

    S = mesh.shape["stage"]
    if S == 1:
        return paged_forward(params, cfg, tokens, cache, positions, active,
                             use_kernel, fresh, last_index)
    uniform_layers_only(cfg, "pipeline serving")
    indexer_unsupported(cfg, "pipeline serving")
    ssm_unsupported(cfg, "pipeline serving")
    streams_unsupported(cfg, "pipeline serving")
    B, T = tokens.shape
    if positions is None:
        positions = cache.lengths[:, None] + jnp.arange(T)[None, :]
    if active is None:
        active = jnp.ones((B,), bool)
    M = num_microbatches or _default_microbatches(B, S)
    if B % M != 0:
        raise ValueError(f"slots {B} not divisible by microbatches {M}")
    if cfg.num_layers % S != 0:
        raise ValueError(f"{cfg.num_layers} layers not divisible by {S} stages")

    x, cos, sin = embed_tokens(params, cfg, tokens, positions)
    mask = make_mask(positions, cache.max_seq) & active[:, None, None]

    quant = cache.quantized
    stage_ops = (cache.k_pages, cache.v_pages)
    if quant:  # scale pools stage-shard their L dim like the code pools
        stage_ops += (cache.k_scale_pages, cache.v_scale_pages)
    body = partial(_paged_pipeline_body, cfg=cfg, S=S, M=M,
                   use_kernel=use_kernel, fresh=fresh, quant=quant)
    y, new_pools = _run_gpipe(
        body, mesh, params["layers"], stage_ops,
        (x, cache.page_table, positions, mask, cos, sin, active), S, M, x)
    logits = final_logits(params, cfg, y)
    new_len = jnp.where(active, cache.lengths + T, cache.lengths)
    return logits, PagedKVCache(new_pools[0], new_pools[1],
                                cache.page_table, new_len, *new_pools[2:])


def paged_pipeline_packed(params: Params, cfg: ModelConfig,
                          tokens: jax.Array, cache, chunk_tokens: jax.Array,
                          chunk_slot: jax.Array, chunk_count: jax.Array,
                          active: jax.Array, window=None, win_len=None,
                          use_kernel: bool = False, *, mesh: Mesh):
    """cache.paged.paged_forward_packed pipelined over `stage`: the same
    rows, layer and head (packed_rows, packed_layer), the layer stack
    and the pool's L dim stage-sharded as in paged_pipeline_forward.
    The S + P*C packed rows go through the stages as ONE microbatch: a
    chunk's rows and its slot's decode row cannot part, and a step that
    streams each stage's weights once is what a decode step costs
    anyway. Pipeline serving keeps per-token pool writes, so there is
    no window here.
    """
    from butterfly_tpu.cache.paged import (
        packed_layer, packed_rows, paged_forward_packed, pool_leaves)
    from butterfly_tpu.models.common import final_logits

    assert window is None and win_len is None
    S = mesh.shape["stage"]
    if S == 1:
        return paged_forward_packed(params, cfg, tokens, cache, chunk_tokens,
                                    chunk_slot, chunk_count, active,
                                    use_kernel=use_kernel)
    uniform_layers_only(cfg, "pipeline serving")
    indexer_unsupported(cfg, "pipeline serving")
    ssm_unsupported(cfg, "pipeline serving")
    streams_unsupported(cfg, "pipeline serving")
    x, rows = packed_rows(params, cfg, tokens, cache, chunk_tokens,
                          chunk_slot, chunk_count, active)
    pools = pool_leaves(cache)
    pad = (None,) * (5 - len(pools))

    def body(layers, *ops):
        *pools, x, rows = ops

        def step(pools, mc, valid, inp):
            # a bubble tick runs on garbage: it writes nothing
            live = rows._replace(ok=rows.ok & valid)

            def layer(x, scanned):
                lp, *pl = scanned
                x, pl, _, _ = packed_layer(x, lp, (*pl, *pad), None, live,
                                           cfg, use_kernel)
                return x, pl[:len(pools)]

            return lax.scan(layer, inp, (layers, *pools))

        outs, pools = _gpipe_schedule(S, 1, x[None], step, tuple(pools))
        return (outs, *pools)

    y, pools = _run_gpipe(body, mesh, params["layers"], pools, (x, rows),
                          S, 1, x)
    logits = final_logits(params, cfg, y[rows.head])[:, 0]
    return logits, pool_leaves(cache, pools), None


def _gpipe_schedule(S: int, M: int, xs, step_fn, carry0):
    """The GPipe tick skeleton shared by the contiguous and paged bodies.

    Runs M + S - 1 ticks inside a stage-manual region; tick t has this
    stage working on microbatch m = t - stage (bubble ticks have m out of
    range). `step_fn(carry, mc, valid, inp) -> (y, carry)` runs this
    stage's local layers on one microbatch and owns all cache write-back
    masking for bubble ticks. xs is [M, mb, ...]; results are recorded
    from the last stage and returned [M, mb, ...] (garbage elsewhere —
    callers slice the last stage's block via out_specs P('stage')).
    """
    stage = lax.axis_index("stage")
    state0 = jnp.zeros_like(xs[0])
    out0 = jnp.zeros_like(xs)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(c, t):
        state, carry, outs = c
        m = t - stage
        valid = (m >= 0) & (m < M)
        mc = jnp.clip(m, 0, M - 1)
        inp = jnp.where(stage == 0, xs[jnp.clip(t, 0, M - 1)], state)
        y, carry = step_fn(carry, mc, valid, inp)
        rec = jnp.where(valid & (stage == S - 1), y, outs[mc])
        outs = lax.dynamic_update_index_in_dim(outs, rec, mc, axis=0)
        state = lax.ppermute(y, "stage", fwd_perm)
        return (state, carry, outs), None

    (_, carry, outs), _ = lax.scan(tick, (state0, carry0, out0),
                                   jnp.arange(M + S - 1))
    return outs, carry


def _paged_pipeline_body(layers, k_pages, v_pages, *ops, cfg: ModelConfig,
                         S: int, M: int, use_kernel: bool, fresh: bool,
                         quant: bool = False):
    """Per-stage GPipe body over the paged pool (manual over stage).

    layers/k_pages/v_pages (and, for int8 pools, the two scale pools that
    lead `ops`) are the local [L/S, ...] stage slice; x, the block table,
    and the per-token aux arrays are full-slot-batch and replicated over
    stage.
    """
    from butterfly_tpu.cache.paged import paged_layer_body

    if quant:
        ksp0, vsp0, x, page_table, positions, mask, cos, sin, active = ops
    else:
        x, page_table, positions, mask, cos, sin, active = ops
        ksp0 = vsp0 = None
    B = x.shape[0]
    mb = B // M

    xs = x.reshape(M, mb, *x.shape[1:])
    tbl_mb = page_table.reshape(M, mb, *page_table.shape[1:])
    pos_mb = positions.reshape(M, mb, *positions.shape[1:])
    mask_mb = mask.reshape(M, mb, *mask.shape[1:])
    cos_mb = cos.reshape(M, mb, *cos.shape[1:])
    sin_mb = sin.reshape(M, mb, *sin.shape[1:])
    act_mb = active.reshape(M, mb)

    def step(carry, mc, valid, inp):
        kp, vp, ksp, vsp = carry
        # bubble ticks redirect their pool writes to the null page via the
        # active mask (the paged analogue of the contiguous path's
        # where(valid) write-back)
        act = act_mb[mc] & valid

        def layer(x, scanned):
            lp, kpl, vpl, *scl = scanned
            out = paged_layer_body(
                x, lp, kpl, vpl, cfg=cfg, page_table=tbl_mb[mc],
                positions=pos_mb[mc], mask=mask_mb[mc], cos=cos_mb[mc],
                sin=sin_mb[mc], active=act, use_kernel=use_kernel,
                fresh=fresh, ksp=scl[0] if scl else None,
                vsp=scl[1] if scl else None)
            return out[0], tuple(out[1:])

        scan_xs = (layers, kp, vp) + ((ksp, vsp) if quant else ())
        y, new = lax.scan(layer, inp, scan_xs)
        if quant:
            return y, new
        return y, (*new, None, None)

    outs, (kp, vp, ksp, vsp) = _gpipe_schedule(
        S, M, xs, step, (k_pages, v_pages, ksp0, vsp0))
    if quant:
        return outs, kp, vp, ksp, vsp
    return outs, kp, vp


def _interleaved_body(layers, ck, cv, *ops, cfg: ModelConfig, S: int,
                      M: int, V: int, fresh: bool = False,
                      quant: bool = False):
    """Interleaved virtual-stage schedule (manual over stage).

    Work unit w = v*M + m: chunk v of microbatch m. Tick t has stage s
    on w = t - s; V*M + S - 1 ticks total. The ppermute ring WRAPS
    (S-1 -> 0): a microbatch leaving the last stage's chunk v re-enters
    stage 0 for chunk v+1. Early wrapped arrivals (they land after one
    hop but are consumed M-S+1 ticks later) sit in a per-microbatch
    buffer on stage 0. int8 caches thread their scale leaves (leading
    `ops`) through the same chunk/microbatch slicing as the code leaves.
    """
    if quant:
        ks, vs, x, positions, mask, cos, sin = ops
    else:
        x, positions, mask, cos, sin = ops
        ks = vs = None
    B = x.shape[0]
    mb = B // M
    Lc = ck.shape[0] // V  # local layers per virtual chunk

    xs = x.reshape(M, mb, *x.shape[1:])
    pos_mb = positions.reshape(M, mb, *positions.shape[1:])
    mask_mb = mask.reshape(M, mb, *mask.shape[1:])
    cos_mb = cos.reshape(M, mb, *cos.shape[1:])
    sin_mb = sin.reshape(M, mb, *sin.shape[1:])

    layers_v = jax.tree.map(lambda a: a.reshape(V, Lc, *a.shape[1:]), layers)
    cache_v = tuple(a.reshape(V, Lc, *a.shape[1:]) if a is not None else None
                    for a in (ck, cv, ks, vs))

    stage = lax.axis_index("stage")
    state0 = jnp.zeros_like(xs[0])
    buf0 = jnp.zeros_like(xs)     # stage-0 holding pen for wrapped states
    out0 = jnp.zeros_like(xs)
    ring = [(i, (i + 1) % S) for i in range(S)]

    def tick(c, t):
        state, buf, cachev, outs = c

        # bank the state that just wrapped onto stage 0 (produced by the
        # last stage at t-1 with work index t-S; destined for chunk
        # (t-S)//M + 1 of microbatch (t-S)%M)
        w_in = t - S
        keep_in = (stage == 0) & (w_in >= 0) & (w_in < V * M - M)
        m_in = jnp.clip(w_in, 0, V * M - 1) % M
        banked = lax.dynamic_update_index_in_dim(buf, state, m_in, 0)
        buf = jnp.where(keep_in, banked, buf)

        w = t - stage
        valid = (w >= 0) & (w < V * M)
        wc = jnp.clip(w, 0, V * M - 1)
        v = wc // M
        m = wc % M

        inj = jnp.where(v == 0, xs[m], buf[m])
        inp = jnp.where(stage == 0, inj, state)

        lyr = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, v, 0, keepdims=False),
            layers_v)
        chunk = tuple(
            None if a is None
            else lax.dynamic_index_in_dim(a, v, 0, keepdims=False)
            for a in cachev)
        mbs = tuple(
            None if a is None
            else lax.dynamic_slice_in_dim(a, m * mb, mb, axis=1)
            for a in chunk)

        y, *new = scan_layers(lyr, cfg, inp, mbs[0], mbs[1],
                              pos_mb[m], mask_mb[m], cos_mb[m],
                              sin_mb[m], fresh, mbs[2], mbs[3])
        new = tuple(new) if quant else (*new, None, None)

        def write_back(a_c, n, o):
            return lax.dynamic_update_slice_in_dim(
                a_c, jnp.where(valid, n, o), m * mb, axis=1)

        chunk = tuple(None if a is None else write_back(a, n, o)
                      for a, n, o in zip(chunk, new, mbs))
        cachev = tuple(
            None if a is None else lax.dynamic_update_index_in_dim(a, cc, v, 0)
            for a, cc in zip(cachev, chunk))

        rec = jnp.where(valid & (stage == S - 1) & (v == V - 1), y, outs[m])
        outs = lax.dynamic_update_index_in_dim(outs, rec, m, 0)
        state = lax.ppermute(y, "stage", ring)
        return (state, buf, cachev, outs), None

    (_, _, cachev, outs), _ = lax.scan(
        tick, (state0, buf0, cache_v, out0),
        jnp.arange(V * M + S - 1))
    flat = tuple(a.reshape(o.shape) for a, o in
                 zip(cachev, (ck, cv, ks, vs)) if a is not None)
    return (outs, *flat)


def _default_microbatches(B: int, S: int) -> int:
    """Largest divisor of B that is <= 2*S (keeps the bubble small without
    violating B % M == 0 for any batch size)."""
    best = 1
    for m in range(1, min(B, 2 * S) + 1):
        if B % m == 0:
            best = m
    return best


def _pipeline_body(layers, ck, cv, *ops, cfg: ModelConfig, S: int, M: int,
                   fresh: bool = False, quant: bool = False):
    """Per-stage GPipe body, contiguous cache (manual over stage).

    layers/ck/cv (and, for int8 caches, the two scale leaves that lead
    `ops`) are the local [L/S, ...] stage slice; x [B,T,D] etc. are
    full-batch and replicated over stage. Returns outs stage-stacked
    (real results only on the last stage — out_specs P('stage'), caller
    slices — no [B,T,D] all-reduce over `stage`).
    """
    if quant:
        ks0, vs0, x, positions, mask, cos, sin = ops
    else:
        x, positions, mask, cos, sin = ops
        ks0 = vs0 = None
    B = x.shape[0]
    mb = B // M

    # [M, mb, ...] microbatch views
    xs = x.reshape(M, mb, *x.shape[1:])
    pos_mb = positions.reshape(M, mb, *positions.shape[1:])
    mask_mb = mask.reshape(M, mb, *mask.shape[1:])
    cos_mb = cos.reshape(M, mb, *cos.shape[1:])
    sin_mb = sin.reshape(M, mb, *sin.shape[1:])

    def step(carry, mc, valid, inp):
        ck, cv, ks, vs = carry
        sl = lambda a: lax.dynamic_slice_in_dim(a, mc * mb, mb, axis=1)
        ck_m, cv_m = sl(ck), sl(cv)
        ks_m = sl(ks) if quant else None
        vs_m = sl(vs) if quant else None

        y, nk, nv, *nsc = scan_layers(layers, cfg, inp, ck_m, cv_m,
                                      pos_mb[mc], mask_mb[mc], cos_mb[mc],
                                      sin_mb[mc], fresh, ks_m, vs_m)

        # write back cache only on valid (non-bubble) ticks
        upd = lambda a, n, o: lax.dynamic_update_slice_in_dim(
            a, jnp.where(valid, n, o), mc * mb, axis=1)
        ck = upd(ck, nk, ck_m)
        cv = upd(cv, nv, cv_m)
        if quant:
            ks = upd(ks, nsc[0], ks_m)
            vs = upd(vs, nsc[1], vs_m)
        return y, (ck, cv, ks, vs)

    outs, (ck, cv, ks, vs) = _gpipe_schedule(S, M, xs, step,
                                             (ck, cv, ks0, vs0))
    if quant:
        return outs, ck, cv, ks, vs
    return outs, ck, cv
