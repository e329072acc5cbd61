#!/usr/bin/env python
"""Compile every Pallas kernel variant on the chip and check it.

Tier-1 runs on the CPU, where a kernel is interpreted or gives way to
`jnp`; only Mosaic on a real chip says whether a kernel compiles. This
program builds each variant the engines use, at the Llama-3-8B head
geometry (32 query heads over 8 KV heads of 128, page 16, the serving
window width), and for each one:

* compiles it and looks for the Mosaic call (`tpu_custom_call`) in the
  compiled HLO text;
* runs it and compares with the `jnp` reference the dense paths use
  (`models.common.attend`, `ops.ring_attention.ring_block_stats_ref`).

Variants: flash fresh; flash warm over a float prefix and over an int8
prefix; paged plain, int8, and both with the write-combined window
segment; ring float and int8. On a host with four or more devices each
runs again under the mesh wrappers the engines call (`*_sharded` on a
tensor=4 mesh: 8 query and 2 KV heads per shard; the ring under
`shard_map` on a seq=4 mesh). `ssm_step`, the Mamba-2 decode step, runs
at granite-4.0-h-small's state geometry (9 layers, 128 slots, 128 heads
of 64, state 128, bfloat16: 2.4 GB) against `ssm_scan` at T == 1 and
the update in place, the state donated as the engine donates it, and
its compiled HLO must hold no copy of the state. `gdn_step`, the Gated
DeltaNet decode step, runs at `olmohybrid7b.batch`'s (24 layers, 64
slots, 15 groups of [96, 384], bfloat16: 1.8 GB) against
models/common.py gdn_step the same way, and a layer-step of each is
timed beside the bytes of one pass over a layer's state. `mamba1_step`,
the Mamba-1 decode step, runs at `jamba2-3b.rollout`'s (26 layers, 128
slots of [16, 5120], bfloat16: 545 MB) against models/common.py
mamba1_step the same way, timed the same way. `latent`, the absorbed
read of a latent-attention model's cached rows (ops/latent_attention.py),
runs at JoyAI-LLM-Flash's geometry (96 slots, 32 heads, rows of 576
values in 640 lanes, a pool of 6 GB in six layers, a window of 256)
and as `latent_rollout` at `xing29b.rollout`'s (128 slots, ten layers, a
table of 144 pages, contexts drawn 64-2,304 and flat at 1,290) against
`jnp` over the same pages, and is timed over one step's layers beside
the bytes the model's count gives the rows. `paged_cell_*`
run ops/paged_attention.py at the cells' own geometries (PAGED_CELLS: 32
slots at contexts of 170 over an int8 pool, over SmallThinker's 28 heads
on 4 with the sliding scalar live and over a tensor=4 shard's 8 on 2; 128
slots at lengths 64-2,304), a window of 256 with 1-8 staged, against
`jnp` over the same pages, and print a call's time beside its rows'
bytes and the seconds the call took to trace and lower and to compile.
`sparse_cell` runs ops/sparse_attention.py at `keye30b.think`'s geometry
(SPARSE_CELL) beside the gather of the selected rows it stands in for,
both branches of cache/paged.py sparse_paged_attend on the same
operands, and times each at the cell's contexts and at the table's ends.
`index_cell_*` run ops/index_scores.py at both selecting cells' geometries
(INDEX_CELLS) beside the gathered view it stands in for, both branches of
cache/paged.py _index_selection on the same operands: the scores and the
selections against each other, a call's time, a live page's, and the
model's index-key bytes against the memory's rate. `select_cell_*` run
ops/select_mask.py at the three arrays of scores those cells select over
(SELECT_CELLS) beside lax.top_k and the running count it stands in for,
both branches of cache/paged.py _selection on the same scores: the two
masks against each other, what each program still sorts, a call's time.
`expert_cell_*` run ops/moe_experts.py at the expert geometries of the
cells whose steps are few rows over int8 codes (EXPERT_CELLS) beside
XLA's dense products of the same operands (models/common.py
dense_experts): parity, then a layer's time of each at 32 and 64 rows
with every held expert touched, the cell's own share and one.

Last, the program `serve` spends its time in — the serving engine's
fused decode block, at the full 8B width with the depth cut to two
layers — is compiled the way the engine compiles it, alone and on the
tensor=4 mesh, and its HLO must hold the Mosaic call (on the mesh, the
all-reduces too): a layer that quietly took the dense gather path
(cache/paged.py) would compile and serve, and only this shows it.

Exit code 0 only if every variant compiled to a Mosaic call and agreed
with its reference. On the CPU backend the kernels are interpreted, so
the run proves nothing about the compiler and exits 1 (`--small` cuts
the shapes so that such a run finishes: a debugging aid, not a check).
The last line of output is one JSON object; `--out FILE` also writes it.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

MOSAIC_CALL = "tpu_custom_call"


def build_cases(small: bool, windows):
    """[(name, kernel_fn, reference_fn, args, sharded_fn or None)].
    kernel/reference take the same args and return comparable arrays;
    `sharded_fn(mesh)` returns (fn, args placed for the mesh)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.paged import (gather_paged_layer,
                                           gather_paged_layer_q,
                                           scales_by_head, scales_by_step)
    from butterfly_tpu.models.common import attend, quantize_kv
    from butterfly_tpu.ops.flash_attention import (flash_attention,
                                                   flash_attention_sharded)
    from butterfly_tpu.ops.paged_attention import (paged_attention,
                                                   paged_attention_sharded)
    from butterfly_tpu.ops.ring_attention import (INVALID_POS,
                                                  ring_block_stats,
                                                  ring_block_stats_ref)

    Nq, Kv, H, page = (8, 4, 128, 16) if small else (32, 8, 128, 16)
    B, T, Sp = (1, 32, 64) if small else (2, 256, 2048)
    S, max_pages, P = (4, 4, 17) if small else (32, 128, 513)
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), dt)

    def f32(x):
        return x.astype(jnp.float32)

    def kv_major_q(x):
        """[.., S, Kv, H] float -> int8 codes [.., Kv, S, H] + scales
        [.., Kv, S] (the pool representation)."""
        codes, scales = quantize_kv(x)
        return jnp.moveaxis(codes, -3, -2), jnp.moveaxis(scales, -2, -1)

    cases = []

    # -- flash ---------------------------------------------------------
    q, k, v = normal(B, T, Nq, H), normal(B, T, Kv, H), normal(B, T, Kv, H)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    causal = pos[:, None, :] <= pos[:, :, None]
    pk, pv = normal(B, Sp, Kv, H), normal(B, Sp, Kv, H)
    plen = jnp.asarray([40] if small else [300, 0], jnp.int32)
    warm_mask = jnp.concatenate(
        [jnp.broadcast_to((jnp.arange(Sp)[None] < plen[:, None])[:, None],
                          (B, T, Sp)), causal], axis=2)

    def flash(q, k, v, pk=None, pv=None, plen=None, pks=None, pvs=None,
              fn=flash_attention):
        return fn(q, k, v, causal=True, prefix_k=pk, prefix_v=pv,
                  prefix_len=plen, prefix_k_scale=pks, prefix_v_scale=pvs)

    def flash_ref(q, k, v, pk=None, pv=None, plen=None, pks=None, pvs=None):
        if pk is None:
            n = q.shape[1]
            return attend(f32(q), f32(k), f32(v), causal[:, :n, :n], None)
        if pks is not None:  # codes [B,Kv,Sp,H] -> float [B,Sp,Kv,H]
            pk = jnp.moveaxis(f32(pk) * pks[..., None], 1, 2)
            pv = jnp.moveaxis(f32(pv) * pvs[..., None], 1, 2)
        return attend(f32(q), jnp.concatenate([f32(pk), f32(k)], 1),
                      jnp.concatenate([f32(pv), f32(v)], 1), warm_mask, None)

    flash_sh = functools.partial(flash, fn=flash_attention_sharded)
    pkq, pks = kv_major_q(pk)
    pvq, pvs = kv_major_q(pv)
    for name, args in (
            ("flash_fresh", (q, k, v)),
            # a prompt that is no whole number of tiles (`generate`)
            ("flash_fresh_t20", (q[:, :20], k[:, :20], v[:, :20])),
            ("flash_warm", (q, k, v, pk, pv, plen)),
            ("flash_warm_int8", (q, k, v, pkq, pvq, plen, pks, pvs))):
        cases.append((name, flash, flash_ref, args, flash_sh))

    # -- paged ---------------------------------------------------------
    # the pool whole, three layers of it, and the middle one attended:
    # a kernel that reads another layer's pages disagrees with its
    # reference
    L, ly = 3, jnp.int32(1)
    qd = normal(S, Nq, H)
    kp, vp = normal(L, P, Kv, page, H), normal(L, P, Kv, page, H)
    table = jnp.asarray(rng.randint(0, P - 1, (S, max_pages)), jnp.int32)
    lens = jnp.asarray(rng.randint(0, max_pages * page + 1, (S,)), jnp.int32)
    lens = lens.at[0].set(0).at[1].set(max_pages * page)
    kq, ks = quantize_kv(jnp.moveaxis(kp, 2, 3))   # [L,P,page,Kv,H] in
    vq, vs = quantize_kv(jnp.moveaxis(vp, 2, 3))
    kpq, vpq = jnp.moveaxis(kq, 2, 3), jnp.moveaxis(vq, 2, 3)  # [L,P,Kv,pg,H]
    ksp = jnp.moveaxis(ks, 2, 3).reshape(L, P, Kv * page)      # kv-major
    vsp = jnp.moveaxis(vs, 2, 3).reshape(L, P, Kv * page)
    smax = max_pages * page

    def paged(qd, kp, vp, ly, table, lens, ksp=None, vsp=None, wk=None,
              wv=None, wcnt=None, wks=None, wvs=None, fn=paged_attention):
        return fn(qd, kp, vp, ly, table, lens, ksp, vsp, win_k=wk, win_v=wv,
                  win_count=wcnt, win_k_scale=wks, win_v_scale=wvs)

    def paged_ref(qd, kp, vp, ly, table, lens, ksp=None, vsp=None, wk=None,
                  wv=None, wcnt=None, wks=None, wvs=None):
        """Dense gather + attend (cache/paged.py's dense path), the
        window's layer appended as one more key segment; a slot with no
        keys reads 0, as the kernel leaves an inactive slot."""
        mask = (jnp.arange(smax)[None] < lens[:, None])[:, None]
        if wk is not None:
            # the whole window as the serving path hands it: the layer's
            wk, wv = wk[ly], wv[ly]
            if wks is not None:
                wks, wvs = (scales_by_head(a[ly], wk.shape[1])
                            for a in (wks, wvs))
            mask = jnp.concatenate(
                [mask, (jnp.arange(wk.shape[2])[None]
                        < wcnt[:, None])[:, None]], 2)
        if ksp is None:
            ck, cv = (f32(gather_paged_layer(a, table, ly)) for a in (kp, vp))
            if wk is not None:  # window [S,Kv,W,H] -> [S,W,Kv,H]
                ck, cv = (jnp.concatenate([a, f32(jnp.moveaxis(w, 1, 2))], 1)
                          for a, w in ((ck, wk), (cv, wv)))
            out = attend(f32(qd)[:, None], ck, cv, mask, None)
        else:
            ck, k_s = gather_paged_layer_q(kp, ksp, table, ly)
            cv, v_s = gather_paged_layer_q(vp, vsp, table, ly)
            if wk is not None:
                ck, cv, k_s, v_s = (
                    jnp.concatenate([a, w], 2) for a, w in
                    ((ck, wk), (cv, wv), (k_s, wks), (v_s, wvs)))
            out = attend(f32(qd)[:, None], ck, cv, mask, None, k_s, v_s)
        return jnp.where(mask.any(-1)[:, :, None, None], out, 0)[:, 0]

    paged_sh = functools.partial(paged, fn=paged_attention_sharded)
    paged_args = [("paged", (qd, kp, vp, ly, table, lens)),
                  ("paged_int8", (qd, kpq, vpq, ly, table, lens, ksp, vsp))]
    for W in windows:
        wk, wv = normal(L, S, Kv, W, H), normal(L, S, Kv, W, H)
        wkq, wks = kv_major_q(jnp.moveaxis(wk, 2, 3))
        wvq, wvs = kv_major_q(jnp.moveaxis(wv, 2, 3))
        wks, wvs = scales_by_step(wks), scales_by_step(wvs)
        wcnt = jnp.asarray(rng.randint(0, W + 1, (S,)), jnp.int32)
        wcnt = wcnt.at[0].set(1).at[1].set(W)
        paged_args += [
            (f"paged_win{W}", (qd, kp, vp, ly, table, lens, None, None,
                               wk, wv, wcnt)),
            (f"paged_int8_win{W}", (qd, kpq, vpq, ly, table, lens, ksp, vsp,
                                    wkq, wvq, wcnt, wks, wvs))]
    for name, args in paged_args:
        cases.append((name, paged, paged_ref, args, paged_sh))

    # -- ring ----------------------------------------------------------
    Tr = 64 if small else 512
    qr, kr, vr = normal(1, Tr, Nq, H), normal(1, Tr, Kv, H), \
        normal(1, Tr, Kv, H)
    q_pos = jnp.arange(Tr, 2 * Tr, dtype=jnp.int32)[None]
    k_pos = jnp.arange(Tr // 2, Tr // 2 + Tr, dtype=jnp.int32)[None]
    k_pos = k_pos.at[0, -3:].set(INVALID_POS)

    def normalized(stats):
        """(m, l, acc / l): the sums are compared as the attention
        output they finalize to — the MXU rounds the probabilities of
        p @ v to bf16, an error that scales with l like acc does."""
        m, l, acc = stats
        return m, l, acc / jnp.maximum(l, 1e-30)[..., None]

    def ring(q, k, v, qp, kp, *scales):
        return normalized(ring_block_stats(q, k, v, qp, kp, *scales))

    def ring_ref(q, k, v, qp, kp, *scales):
        k, v = (a if scales else f32(a) for a in (k, v))
        return normalized(
            ring_block_stats_ref(f32(q), k, v, qp, kp, *scales))

    cases.append(("ring", ring, ring_ref, (qr, kr, vr, q_pos, k_pos), None))
    krq, krs = kv_major_q(kr)
    vrq, vrs = kv_major_q(vr)
    cases.append(("ring_int8", ring, ring_ref,
                  (qr, krq, vrq, q_pos, k_pos, krs, vrs), None))
    return cases


def ring_sharded_case(small: bool):
    """The ring as the SP paths run it: `parallel.sequence.ring_attention`
    under shard_map over a seq=4 mesh, against dense causal attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.models.common import attend
    from butterfly_tpu.parallel.sequence import ring_attention

    Nq, Kv, H, T = (8, 4, 128, 64) if small else (32, 8, 128, 1024)
    mesh = make_mesh(MeshConfig(seq=4), jax.devices()[:4])
    rng = np.random.RandomState(1)
    seq = NamedSharding(mesh, P(None, "seq"))
    q, k, v = (jax.device_put(jnp.asarray(rng.standard_normal((1, T, n, H)),
                                          jnp.bfloat16), seq)
               for n in (Nq, Kv, Kv))
    pos = jax.device_put(jnp.arange(T, dtype=jnp.int32)[None], seq)
    fn = jax.shard_map(
        lambda q, k, v, p: ring_attention(q, k, v, p, p), mesh=mesh,
        in_specs=(P(None, "seq"),) * 4, out_specs=P(None, "seq"),
        axis_names={"seq"}, check_vma=False)

    def ref(q, k, v, p):
        f = jnp.float32
        return attend(q.astype(f), k.astype(f), v.astype(f),
                      p[:, None, :] <= p[:, :, None], None)

    return mesh, fn, ref, (q, k, v, pos)


def tp_place(mesh, name, args):
    """Operands laid out as the partitioner lays them out on a tensor
    mesh: heads over `tensor` (parallel/partition.py paged_cache_specs,
    kv_window_specs, warm_prefix_specs), everything else replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = "tensor"
    if name.startswith("flash"):
        quant = len(args) > 6
        pre = P(None, t, None, None) if quant else P(None, None, t, None)
        specs = [P(None, None, t, None)] * 3 + [pre, pre, P()] \
            + [P(None, t, None)] * 2
    else:  # q, pools, layer, table, lens, pool scales, window, count, scales
        specs = [P(None, t, None)] + [P(None, None, t, None, None)] * 2 \
            + [P(), P(), P()] + [P(None, None, t)] * 2 \
            + [P(None, None, t, None, None)] * 2 + [P()] \
            + [P(None, None, None, t)] * 2
    return tuple(None if a is None
                 else jax.device_put(a, NamedSharding(mesh, s))
                 for a, s in zip(args, specs))


def serving_block_hlo(small: bool, mesh):
    """Compiled HLO of the serving engine's packed mixed block (its S
    decode rows beside one 32-token chunk, write-combined int8 window)
    at the 8B width,
    two layers deep, built exactly as `serve` builds it; plus the kernel
    call sites the engine recorded while it traced."""
    import jax
    import jax.numpy as jnp

    from butterfly_tpu.core.config import RuntimeConfig, llama3_8b
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.quant.int8 import init_params_by_leaf

    cfg = llama3_8b().replace(num_layers=2, max_seq_len=2048)
    if small:
        cfg = cfg.replace(vocab_size=512, hidden_size=256, num_heads=8,
                          num_kv_heads=4, intermediate_size=512)
    S, k = 8, 4
    rt = RuntimeConfig(max_batch_size=S, max_seq_len=256, kv_quant="int8",
                       decode_steps_per_tick=k)
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8",
                                 mesh=mesh)
    eng = ServingEngine(Model(cfg), params, rt, mesh=mesh, use_kernels=True)
    C = 32  # one packed prefill chunk beside the S decode rows
    eng._ensure_window(k * C)
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    with eng._mesh_ctx():
        hlo = eng._mixed_block_prog(k, C, 1).lower(
            eng.params, i32((S,)), i32((S,)), eng.cache, eng._kv_window,
            eng._win_len, i32((S, eng.cache.max_seq)), i32((S,)),
            jnp.ones((S,), bool), jnp.zeros((S,), jnp.float32),
            jnp.full((S,), -1, jnp.int32), jnp.full((S,), k, jnp.int32),
            0, 1.0, jax.random.PRNGKey(0)).compile().as_text()
    return hlo, dict(eng.kernel_calls)


def run_serving_block(name, small, mesh, want):
    rec = {"name": name, "ok": False}
    t0 = time.perf_counter()
    try:
        hlo, calls = serving_block_hlo(small, mesh)
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        rec["hlo_has"] = {w: w in hlo for w in want}
        rec["kernel_calls"] = calls
        rec["ok"] = bool(all(rec["hlo_has"].values())
                         and "dense_fallback" not in calls
                         and any(c.startswith("paged_int8_win")
                                 for c in calls))
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


def state_copies(hlo: str, h) -> list:
    """The instructions of a compiled HLO text that COPY the recurrent
    state h [Ls, S, ...] (either kind), whole or one layer of it: a `copy`, or a
    fusion the compiler named for one (`copy_bitcast_fusion`)."""
    import re
    whole = ",".join(map(str, h.shape))
    layer = ",".join(map(str, h.shape[1:]))
    made = re.compile(rf"^\s*(?:ROOT )?%(\S*copy\S*) = \(?\w+\[(?:1,)?"
                      rf"(?:{whole}|{layer})\]")
    return [m.group(1) for m in map(made.match, hlo.splitlines()) if m]


def expert_moves(hlo: str, experts) -> list:
    """The instructions of a compiled HLO text that MAKE a value of the
    shape of the experts' layer-stacked codes ([L, E, D, F] gate and up,
    [L, E, F, D] down: `experts` the stacked leaves, arrays or shapes)
    or of one layer's codes [E, ..] in the device's memory: a `copy`, a
    `dynamic-slice`, a fusion's result, whatever is not a parameter, a
    tuple's element or a bitcast (those name a value and make none; a
    loop's carry is a tuple), in any computation that is not a fusion's
    own (inside one, a slice of the stack is how the dense products READ
    a layer where it lies). What a program that hands ops/moe_experts.py
    the WHOLE stack and the layer's index holds none of: one layer's
    slice handed to a custom call is materialised, 604 MB a layer at
    GLM-5's widths (PERF.md, PR 61)."""
    import re
    shapes = set()
    for name in ("w_gate", "w_up", "w_down"):
        dims = experts[name]["q8"].shape
        shapes |= {",".join(map(str, dims)), ",".join(map(str, dims[1:]))}
    fused = set(re.findall(r" fusion\(.*?calls=%([^\s,)]+)", hlo))
    head = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
    made = re.compile(r"^\s*(?:ROOT )?%(\S+) = s8\[(?:1,)?([\d,]+)\]\S* "
                      r"(?!parameter|get-tuple-element|bitcast)[\w-]+\(")
    found, inside = [], False
    for line in hlo.splitlines():
        m = head.match(line)
        if m:
            inside = m.group(1) not in fused
        elif inside:
            m = made.match(line)
            if m and m.group(2) in shapes:
                found.append(m.group(1))
    return found


def window_moves(hlo: str, leaves,
                 kinds=("copy", "transpose", "fusion")) -> list:
    """The instructions INSIDE a compiled HLO text's loops that make a
    value of a window leaf's whole shape [L, S, Kv, W, H] or of one
    layer's slice of it: a `copy`, a `transpose` or a fusion (`kinds`;
    a carried leaf that every layer COMPUTES anew, the residual streams
    of a model of n, is held to the first two) in any
    computation that is some `while`'s body. (The loops' carries, the
    Mosaic writer's aliased result and a chunk's one-slot view are none
    of these: tuples, a custom call, another shape.) `leaves`: arrays or
    shapes-and-dtypes, None skipped. What a window that rides the layer
    scan whole and is written in place leaves none of (PERF.md, PR 47)."""
    import re
    shapes = set()
    for a in leaves:
        if a is not None:
            shapes |= {",".join(map(str, a.shape)),
                       ",".join(map(str, a.shape[1:]))}
    bodies = set(re.findall(r"body=%([^\s,)]+)", hlo))
    head = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
    made = re.compile(
        r"^\s*(?:ROOT )?%(\S+) = (.*?) (?:" + "|".join(kinds) + r")\(")
    found, inside = [], False
    for line in hlo.splitlines():
        m = head.match(line)
        if m:
            inside = m.group(1) in bodies
        elif inside:
            m = made.match(line)
            if m and any(dims in shapes for dims in
                         re.findall(r"\w+\[(?:1,)*([\d,]+)\]", m.group(2))):
                found.append(m.group(1))
    return found


def index_views(hlo: str, cache, key_width: int) -> list:
    """The instructions INSIDE a compiled HLO text's loops that make the
    index keys of every slot's whole table as ONE value: a result
    [S * S_max, w], [S, S_max, (1,) w], [S * mp, page, w] or
    [S, mp, (1,) page, w], w the width of a cached key (the pool's rows,
    whole lanes) or of the key itself (`key_width`, index_head_dim:
    XLA's reader cuts the lanes behind a key off). What a decode
    row's scores were made from until PR 53 (gather_paged_layer of the
    index-key pool, a relayout behind it) and what a program whose rows
    score through ops/index_scores.py holds none of; a chunk's view is
    ONE slot's, another shape. cache: the paged cache, arrays or shapes
    (None for a model without an indexer: nothing to find)."""
    import re
    if cache.ki_pages is None:
        return []
    S, mp = cache.page_table.shape
    page, width = cache.ki_pages.shape[3:]
    w = rf"(?:{width}|{key_width})"
    view = re.compile(
        rf"\w+\[(?:{S * mp * page},{w}|{S},{mp * page},(?:1,)?{w}"
        rf"|{S * mp},{page},{w}|{S},{mp},(?:1,)?{page},{w})\]")
    bodies = set(re.findall(r"body=%([^\s,)]+)", hlo))
    head = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
    made = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) ")
    found, inside = [], False
    for line in hlo.splitlines():
        m = head.match(line)
        if m:
            inside = m.group(1) in bodies
        elif inside:
            m = made.match(line)
            if m and view.match(m.group(2)):
                found.append(m.group(1))
    return found


def span_sorts(hlo: str, span: int) -> list:
    """The instructions of a compiled HLO text that ORDER or SCAN a
    whole row of the table's span: a `sort` whose result's last dim is
    `span` (lax.top_k of every position's index score: a full bitonic
    sort for one threshold), and a `reduce-window` over [.., span] or
    over its lanes [.., span / 128, 128] (the running count of the ties
    at that threshold). What a selection made by COUNTING leaves none
    of (ops/select_mask.py; PERF.md, PR 55); the experts' router sorts
    its few logits a row, another last dim."""
    import re
    tail = rf"(?:{span}|{max(span // 128, 1)},128)\]"
    made = re.compile(
        rf"^\s*(?:ROOT )?%(\S+) = \(?\w+\[(?:\d+,)*(?:{span}\]"
        rf"[^=]*? sort\(|{tail}[^=]*? reduce-window\()")
    return [m.group(1) for m in map(made.match, hlo.splitlines()) if m]


def selecting_calls(config: dict, hlo: str):
    """How many instructions of a compiled HLO text the benchmark's
    pattern for the selecting latent read tells
    (servebench/dsa_peaks.py dsa_patterns: the call's name and its
    result's [.., heads, kv_lora_rank], on the name as a trace gives
    it, servebench/xplane.py clean); None for a configuration without
    an indexer over a latent cache, or on a checkout older than the
    pattern."""
    import re
    try:
        from servebench.dsa_peaks import dsa_patterns, is_dsa
        from servebench.xplane import clean
    except ImportError:
        return None
    if not is_dsa(config):
        return None
    call = dsa_patterns(config)["call"]
    return sum(bool(call.search(clean(m)))
               for m in re.findall(r"^\s*(?:ROOT )?(%\S+ = \S+)", hlo, re.M))


def cell_blocks(config: dict, hlo_dir=None,
                blocks=("mixed", "decode")) -> list:
    """A cell's two block programs, the mixed block (one chunk of C
    beside the decode rows) and the decode block, at the sizes of its
    configuration file, compiled for a DESCRIBED v5e (no chip: shapes
    only, `python3 tools/chip_kernels.py --cell <config.json>` under
    JAX_PLATFORMS=cpu), built as engine/serving.py builds them
    (_packed_scan over paged_forward_packed, kernels on, the cursor, the
    cache, the window and its counts donated). A record a program: what
    the compiler says it holds (arguments, temporaries, whether that
    fits the chip's 15.75 GiB), the Mosaic calls by name, and the
    instructions inside its loops that move a carried leaf: a copy,
    transpose or fusion of a window leaf's shape, a copy or transpose
    of the residual streams' [n, rows, 1, D] (window_moves); for a model
    with an indexer the values of the shape of the table's index keys
    as a view of every slot (index_views: none since PR 53, the decode
    rows score through ops/index_scores.py) and the sorts and running
    counts over a row of the table's span (span_sorts: none since
    PR 55, the selection counts in ops/select_mask.py); for a model
    with a recurrent state the copies of it, whole or a layer
    (state_copies); for a
    latent cache whose rows an indexer selects, the instructions the
    benchmark's pattern tells as the selecting read (selecting_calls);
    for a model of experts in int8 codes the instructions that make a
    value of the codes' shape, stacked or one layer's (expert_moves:
    none where the step takes ops/moe_experts.py, which reads the stack
    where it lies). `blocks`: which of the two to compile.
    The compiler's figures were the chip's to the megabyte (PR 41).
    hlo_dir: where to write each program's compiled text
    (`<name>.hlo.txt`: what a trace will name, and what two checkouts'
    programs are compared by)."""
    import re
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from butterfly_tpu.cache.paged import (
        init_kv_window, init_paged_cache, paged_forward_packed, ring_pages)
    from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
    from butterfly_tpu.engine.serving import _packed_scan
    from butterfly_tpu.quant.int8 import init_params_by_leaf
    from servebench.launcher import model_fields

    cfg = ModelConfig(**model_fields(config))
    sv = config["serve"]
    rt = RuntimeConfig(max_batch_size=sv["max_batch"],
                       max_seq_len=sv["max_seq"], page_size=sv["page_size"],
                       kv_quant=sv.get("kv_quant", "none"),
                       num_pages=sv.get("num_pages", 0),
                       decode_steps_per_tick=sv["decode_steps_per_tick"])
    S, k = rt.max_batch_size, rt.decode_steps_per_tick
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = on_chip(jax.eval_shape(lambda: init_params_by_leaf(
        cfg, jax.random.PRNGKey(0), quant=sv.get("quant", "none"))))
    # the sliding layers' rows in a ring of their own where the engine
    # would keep them so (ServingEngine.__init__)
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, rt, ring=ring_pages(cfg, rt)))
    window = on_chip(jax.eval_shape(
        lambda: init_kv_window(cache, rt.inflight_blocks * k * C)))
    i32 = jnp.int32
    args = [params, sds((S,), i32), sds((S,), i32), on_chip(cache), window,
            sds((S,), i32), sds((S, rt.max_seq_len), i32), sds((S,), i32),
            sds((S,), bool), sds((S,), jnp.float32), sds((S,), i32),
            sds((S,), i32), 0, 1.0, sds((2,), jnp.uint32)]
    donated = (2, 3, 4, 5)
    state = None
    if cfg.has_ssm:
        from butterfly_tpu.cache.ssm_state import init_ssm_state
        state = on_chip(jax.eval_shape(lambda: init_ssm_state(cfg, S)))
        args.append(state)
        donated += (15,)
    real_backend, out = jax.default_backend, []
    jax.default_backend = lambda: "tpu"     # kernels compile, not interpret
    moe = params.get("sparse", params["layers"]).get("moe", {})
    coded = isinstance(moe.get("w_gate"), dict)
    try:
        for name, P in (("mixed", 1), ("decode", 0)):
            if name not in blocks:
                continue
            rec = {"name": name, "rows": S + P * C, "ok": False}
            t0 = time.perf_counter()
            try:
                compiled = jax.jit(
                    partial(_packed_scan, cfg, paged_forward_packed, k, C, P,
                            use_kernel=True),
                    static_argnums=(12, 13),
                    donate_argnums=donated).lower(*args).compile()
            except Exception as e:
                rec["error"] = f"{type(e).__name__}: {e}"[:1500]
                out.append(rec)
                continue
            hlo, mem = compiled.as_text(), compiled.memory_analysis()
            if hlo_dir:
                Path(hlo_dir).mkdir(parents=True, exist_ok=True)
                (Path(hlo_dir) / f"{name}.hlo.txt").write_text(hlo)
            held = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
                + mem.output_size_in_bytes - mem.alias_size_in_bytes
            n = getattr(cfg, "hc_mult", 0)      # 0 on an older checkout
            streams = [sds((n, S + P * C, 1, cfg.hidden_size),
                           jnp.dtype(cfg.dtype))] if n else []
            rec.update(
                compile_s=round(time.perf_counter() - t0, 1),
                argument_gb=round(mem.argument_size_in_bytes / 1e9, 3),
                temp_gb=round(mem.temp_size_in_bytes / 1e9, 3),
                held_gib=round(held / 2 ** 30, 3),
                mosaic_calls=sorted(set(re.findall(
                    r"%(\w+?)[.\d]* = [^=]*custom-call\([^\n]*"
                    + MOSAIC_CALL, hlo))),
                window_moves=window_moves(hlo, jax.tree.leaves(window)),
                stream_moves=window_moves(hlo, streams,
                                          kinds=("copy", "transpose")),
                index_views=index_views(hlo, cache, cfg.index_head_dim),
                span_sorts=span_sorts(hlo, rt.max_seq_len)
                if cfg.has_indexer else [],
                state_copies=state_copies(hlo, state.h) if state else [],
                expert_moves=expert_moves(hlo, moe) if coded else [])
            rec["ok"] = held < 15.75 * 2 ** 30 and not rec["window_moves"] \
                and not rec["state_copies"] \
                and not ("moe_experts" in rec["mosaic_calls"]
                         and rec["expert_moves"]) \
                and not rec["stream_moves"] and not rec["index_views"] \
                and not rec["span_sorts"] and bool(rec["mosaic_calls"])
            told = selecting_calls(config, hlo)
            if told is not None:
                # a latent cache whose rows an indexer selects: every
                # decode row's read is the call the benchmark's pattern
                # tells, and the plain latent read is not in the program
                rec["selecting_calls"] = told
                rec["ok"] = rec["ok"] and told > 0 \
                    and "latent_attention" not in rec["mosaic_calls"]
            out.append(rec)
    finally:
        jax.default_backend = real_backend
    return out


def run_ssm_step(name, small, want):
    """ops/ssm_step.py at granite-4.0-h-small's state geometry, the
    layer index traced, against the `jnp` step it replaces
    (cache/ssm_state.py: ssm_scan at T == 1, then the update in place);
    a fifth of the slots do not decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.ssm_state import decode_rows_step
    from butterfly_tpu.core.config import granite_4_h_small, tiny

    cfg = tiny("granite_hybrid", ssm_state=128) if small \
        else granite_4_h_small()
    Lm, S = (3, 4) if small else (9, 128)
    Nh, Hd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    h = jax.random.normal(ks[0], (Lm, S, Nh, Hd, N), jnp.bfloat16)
    u = jax.random.normal(ks[1], (S, 1, cfg.ssm_conv_dim))
    dt = jax.random.normal(ks[2], (S, 1, Nh))
    mp = {"dt_bias": jax.random.normal(ks[3], (Nh,)),
          "A_log": jax.random.uniform(ks[4], (Nh,), minval=-1., maxval=1.),
          "D": jax.random.normal(ks[5], (Nh,))}
    count = (jax.random.uniform(ks[6], (S,)) > 0.2).astype(jnp.int32)
    m = jnp.int32(Lm // 2)

    def step(use_kernel):
        return lambda h, m, u, dt, mp, count: decode_rows_step(
            h, m, u, dt, mp, cfg, count, use_kernel)

    rec = {"name": name, "ok": False}
    t0 = time.perf_counter()
    try:
        args = (m, u, dt, mp, count)
        want_y, want_h = jax.jit(step(False))(h, *args)
        dead = np.flatnonzero(np.asarray(count) == 0)
        kept = h[m][dead]
        compiled = jax.jit(step(True), donate_argnums=0).lower(
            h, *args).compile()
        hlo = compiled.as_text()
        y, h = jax.block_until_ready(compiled(h, *args))    # h is consumed
        rec["compile_run_s"] = round(time.perf_counter() - t0, 2)
        rec["hlo_has"] = {w: w in hlo for w in want}
        rec["state_copies"] = state_copies(hlo, h)

        @jax.jit        # one fused reduction: no float32 copy of 2.4 GB
        def err(a, b):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            return jnp.max(jnp.abs(a - b) / (1 + jnp.abs(b)))

        errs = [float(err(y, want_y)), float(err(h, want_h))]
        rec["max_err"] = round(max(errs), 5)
        rec["dead_rows_kept"] = bool(jnp.array_equal(h[m][dead], kept))
        rec["ok"] = bool(np.isfinite(max(errs)) and max(errs) < 3e-2
                         and rec["dead_rows_kept"] and len(dead)
                         and not rec["state_copies"]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


def time_layer_steps(rec, every_layer, h, rest):
    """A layer-step's time of a recurrent kind's decode step, kernel and
    `jnp`: every_layer(use_kernel) is a jitted scan of every layer of
    the carried state h (donated) that returns (h, a sum a layer). Into
    rec: the microseconds and GB/s of each beside the bytes of one pass
    (a layer's state read and written), and whether the sums are finite.
    Returns h as the last run left it."""
    import jax
    import numpy as np
    layers = h.shape[0]
    layer_mb = 2 * h[0].size * h.dtype.itemsize / 1e6
    for label, use_kernel in (("kernel", True), ("jnp", False)):
        fn = every_layer(use_kernel)
        h, _ = fn(h, *rest)
        jax.block_until_ready(h)
        t1 = time.perf_counter()
        for _ in range(10):
            h, sums = fn(h, *rest)
        jax.block_until_ready(h)
        us = (time.perf_counter() - t1) / 10 / layers * 1e6
        rec[label + "_us_a_layer_step"] = round(us, 1)
        rec[label + "_gb_s"] = round(layer_mb / us * 1e3, 1)
    rec["layer_pass_mb"] = round(layer_mb, 1)
    rec["finite"] = bool(np.isfinite(np.asarray(sums)).all())
    return h


def run_gdn_step(name, small, want):
    """ops/gdn_step.py at `olmohybrid7b.batch`'s state geometry (24
    layers, 64 slots, 15 groups of [96, 384], bfloat16: 1.8 GB), the
    layer index traced, against the `jnp` step it replaces
    (models/common.py gdn_step: one reduction over the layer's state and
    the update in place), both through cache/ssm_state.py
    _DeltaNet.decode; a fifth of the slots do not decode. Then a
    layer-step's time, kernel and `jnp`, over a scan of every layer
    with the state carried and donated as the engine's block does,
    beside the bytes of one pass (a layer's state read and written)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.ssm_state import _DeltaNet, state_shapes
    from butterfly_tpu.core.config import olmo_hybrid_7b, tiny

    cfg = tiny("olmo_hybrid", gdn_key_dim=32, gdn_value_dim=192,
               dtype="bfloat16") if small \
        else olmo_hybrid_7b().replace(dtype="bfloat16")
    Ls, S = (3, 4) if small else (cfg.num_ssm_layers, 64)
    H = cfg.gdn_heads
    shape = (Ls,) + state_shapes(cfg, S)["h"][1:]
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    h = jax.random.normal(ks[0], shape, jnp.bfloat16)
    u = jax.random.normal(ks[1], (S, 1, cfg.gdn_conv_dim))
    aux = (None, jax.random.normal(ks[2], (S, 1, H)),
           3 * jax.random.normal(ks[3], (S, 1, H)))
    gp = {"dt_bias": jax.random.normal(ks[4], (H,)),
          "A_log": jax.random.uniform(ks[5], (H,), minval=-1., maxval=1.)}
    count = (jax.random.uniform(ks[6], (S,)) > 0.2).astype(jnp.int32)
    m = jnp.int32(Ls // 2)

    def step(use_kernel):
        return lambda h, m, u, aux, gp, count: _DeltaNet.decode(
            h, m, u, aux, gp, cfg, count, use_kernel)

    def every_layer(use_kernel):
        def run(h, u, aux, gp, count):
            def body(h, m):
                o, h = step(use_kernel)(h, m, u, aux, gp, count)
                return h, o.sum()
            return jax.lax.scan(body, h, jnp.arange(Ls))
        return jax.jit(run, donate_argnums=0)

    rec = {"name": name, "ok": False}
    t0 = time.perf_counter()
    try:
        args = (m, u, aux, gp, count)
        want_o, want_h = jax.jit(step(False))(h, *args)
        dead = np.flatnonzero(np.asarray(count) == 0)
        live = int(np.flatnonzero(np.asarray(count) > 0)[0])
        kept, was = h[m][dead], h[m][live]
        compiled = jax.jit(step(True), donate_argnums=0).lower(
            h, *args).compile()
        hlo = compiled.as_text()
        o, h = jax.block_until_ready(compiled(h, *args))    # h is consumed
        rec["compile_run_s"] = round(time.perf_counter() - t0, 2)
        rec["hlo_has"] = {w: w in hlo for w in want}
        rec["state_copies"] = state_copies(hlo, h)

        @jax.jit        # one fused reduction: no float32 copy of 1.8 GB
        def err(a, b):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            return jnp.max(jnp.abs(a - b) / (1 + jnp.abs(b)))

        # a row that does not decode reads out zero from the kernel and
        # its state from the `jnp` step: nothing takes it
        decodes = (count > 0).reshape(S, 1, 1, 1)
        errs = [float(err(jnp.where(decodes, o, 0),
                          jnp.where(decodes, want_o, 0))),
                float(err(h, want_h))]
        del want_h
        rec["max_err"] = round(max(errs), 5)
        rec["dead_rows_kept"] = bool(jnp.array_equal(h[m][dead], kept))
        rec["live_rows_moved"] = not bool(jnp.array_equal(h[m][live], was))
        h = time_layer_steps(rec, every_layer, h, args[1:])
        rec["ok"] = bool(np.isfinite(max(errs)) and max(errs) < 3e-2
                         and rec["dead_rows_kept"] and len(dead)
                         and rec["live_rows_moved"]
                         and not rec["state_copies"] and rec["finite"]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


def run_mamba1_step(name, small, want):
    """ops/mamba1_step.py at `jamba2-3b.rollout`'s state geometry (26
    layers, 128 slots, [16, 5120], bfloat16: 545 MB), the layer index
    traced, against the `jnp` step it replaces (models/common.py
    mamba1_step: the update in place and a readout that forms it a
    second time), both through cache/ssm_state.py _Mamba1.decode; a
    fifth of the slots do not decode. Then a layer-step's time, kernel
    and `jnp`, over a scan of every layer with the state carried and
    donated as the engine's block does, beside the bytes of one pass (a
    layer's state read and written). Where Mosaic's exponential is not
    XLA's the two differ in the last place: `y_ulps_max` and
    `y_differ_share` say by how much of float32 and where, and
    `state_differ_share` what share of the stored values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.ssm_state import _Mamba1, state_shapes
    from butterfly_tpu.core.config import jamba2_3b, tiny

    cfg = tiny("jamba", dtype="bfloat16") if small \
        else jamba2_3b().replace(dtype="bfloat16")
    Lm, S = (3, 12) if small else (cfg.num_ssm_layers, 128)
    N, Di, R = cfg.mamba1_state, cfg.mamba1_inner, cfg.mamba1_dt_rank
    shape = (Lm,) + state_shapes(cfg, S)["h"][1:]
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    h = jax.random.normal(ks[0], shape, jnp.bfloat16)
    u = jax.random.normal(ks[1], (S, 1, Di))
    # rates as the family's seeded stack has them (models/common.py
    # MAMBA1_SEEDS: A in 1-16, dt in .001-.1), so that a state is
    # neither kept whole nor forgotten in one step
    mp = {"x_proj": jax.random.normal(ks[2], (Di, R + 2 * N)) * Di ** -0.5,
          "dt_proj": jax.random.normal(ks[3], (R, Di)) * R ** -0.5,
          "dt_bias": jnp.log(jnp.expm1(jax.random.uniform(
              ks[4], (Di,), minval=1e-3, maxval=0.1))),
          "A_log": jnp.log(jax.random.uniform(ks[5], (N, Di), minval=1.,
                                              maxval=16.)),
          "D": jax.random.normal(ks[6], (Di,))}
    count = (jax.random.uniform(ks[7], (S,)) > 0.2).astype(jnp.int32)
    m = jnp.int32(Lm // 2)

    def step(use_kernel):
        return lambda h, m, u, mp, count: _Mamba1.decode(
            h, m, u, (None,), mp, cfg, count, use_kernel)

    def every_layer(use_kernel):
        def run(h, u, mp, count):
            def body(h, m):
                y, h = step(use_kernel)(h, m, u, mp, count)
                return h, y.sum()
            return jax.lax.scan(body, h, jnp.arange(Lm))
        return jax.jit(run, donate_argnums=0)

    rec = {"name": name, "ok": False}
    t0 = time.perf_counter()
    try:
        args = (m, u, mp, count)
        want_y, want_h = jax.jit(step(False))(h, *args)
        dead = np.flatnonzero(np.asarray(count) == 0)
        live = int(np.flatnonzero(np.asarray(count) > 0)[0])
        kept, was = h[m][dead], h[m][live]
        compiled = jax.jit(step(True), donate_argnums=0).lower(
            h, *args).compile()
        hlo = compiled.as_text()
        y, h = jax.block_until_ready(compiled(h, *args))    # h is consumed
        rec["compile_run_s"] = round(time.perf_counter() - t0, 2)
        rec["hlo_has"] = {w: w in hlo for w in want}
        rec["state_copies"] = state_copies(hlo, h)

        @jax.jit        # fused reductions: no float32 copy of the state
        def read(y, want_y, h, want_h):
            decodes = (count > 0)[:, None, None]
            a, b = jnp.where(decodes, y, 0), jnp.where(decodes, want_y, 0)
            ulp = jnp.abs(a - b) / jnp.maximum(
                jnp.abs(b) * 2.0 ** -23, 2.0 ** -126)
            hf, wf = h.astype(jnp.float32), want_h.astype(jnp.float32)
            return (jnp.max(jnp.abs(a - b) / (1 + jnp.abs(b))),
                    jnp.max(jnp.abs(hf - wf) / (1 + jnp.abs(wf))),
                    jnp.max(ulp), jnp.mean(a != b), jnp.mean(h[m] != want_h[m]))

        # a row that does not decode reads out zero from the kernel and
        # its state from the `jnp` step: nothing takes it
        *errs, ulps, y_share, h_share = map(float, read(y, want_y, h, want_h))
        del want_h
        rec["max_err"] = round(max(errs), 5)
        rec["y_ulps_max"] = round(ulps, 1)
        rec["y_differ_share"] = round(y_share, 6)
        rec["state_differ_share"] = round(h_share, 6)
        rec["dead_rows_kept"] = bool(jnp.array_equal(h[m][dead], kept))
        rec["live_rows_moved"] = not bool(jnp.array_equal(h[m][live], was))
        h = time_layer_steps(rec, every_layer, h, args[1:])
        rec["ok"] = bool(np.isfinite(max(errs)) and max(errs) < 3e-2
                         and rec["dead_rows_kept"] and len(dead)
                         and rec["live_rows_moved"]
                         and not rec["state_copies"] and rec["finite"]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: the geometries of the cells whose steps are few rows over experts
#: in int8 codes, for ops/moe_experts.py: name -> layers of experts in
#: the stack, experts HELD, of E the router ranges over, k a row, D, F,
#: and the experts of the held a step of the cell touches (the cell's
#: own `experts_touched_share`: 69.5 % of 16, 85.3 % of 128, 95.4 % of
#: 64; ledger, PR 60)
EXPERT_CELLS = {
    "expert_cell_glm5": (10, 16, 256, 8, 6144, 2048, 11),
    "expert_cell_keye": (8, 128, 128, 8, 2048, 768, 109),
    "expert_cell_smallthinker": (16, 64, 64, 6, 2560, 768, 61),
}


def seeded_experts(key, L: int, E: int, D: int, F: int):
    """A stack of experts as the kernel and the dense products take it:
    int8 codes drawn a layer at a time (a draw of the whole stack holds
    four times its bytes), scales that keep a row of unit size near unit
    size through an expert."""
    import jax
    import jax.numpy as jnp

    def codes(key, shape, contracted):
        def layer(k):
            return jax.lax.bitcast_convert_type(
                jax.random.bits(k, shape, jnp.uint8), jnp.int8)
        s = jax.random.uniform(jax.random.fold_in(key, 1),
                               (L, E, 1, shape[-1]), minval=0.5, maxval=1.5)
        return {"q8": jax.jit(lambda ks: jax.lax.map(layer, ks))(
                    jax.random.split(key, L)),
                "s": (s / (74.0 * contracted ** 0.5)).astype(jnp.bfloat16)}

    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": codes(kg, (E, D, F), D), "w_up": codes(ku, (E, D, F), D),
            "w_down": codes(kd, (E, F, D), F)}


def run_expert_cell(name, small, want):
    """ops/moe_experts.py at a cell's expert geometry (EXPERT_CELLS),
    the layer index traced over a stack as the engine's layer loop
    rides it, beside XLA's dense products of the same operands
    (models/common.py dense_experts, the stack sliced by the scan as
    the programs slice it today): parity of the two on this backend
    (the untouched experts' gates are zero in both), then a layer's
    time of each at 32 and at 64 rows with ALL held experts touched, the
    cell's own share and ONE, with the GB/s over the touched experts'
    codes; the dense products stream every expert whatever is
    touched."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.models.common import dense_experts
    from butterfly_tpu.ops.moe_experts import moe_experts

    L, E, of, k, D, F, cell = (2, 8, 16, 2, 256, 384, 5) if small \
        else EXPERT_CELLS[name]
    per_row = max(1, round(k * E / of))
    act = jax.nn.silu
    rec = {"name": name, "ok": False, "expert_mb": round(3 * D * F / 1e6, 2)}
    t0 = time.perf_counter()

    def gates(rows, touched):
        """comb [rows, E]: each row's gate 1/k on `per_row` of `touched`
        experts spread over the held, every one of them chosen."""
        ids = np.linspace(0, E - 1, touched).round().astype(int)
        comb = np.zeros((rows, E), np.float32)
        for r in range(rows):
            for j in range(min(per_row, touched)):
                comb[r, ids[(r * per_row + j) % touched]] = 1.0 / k
        return jnp.asarray(comb)

    def kernel_layers(x, comb, ex):
        def body(x, l):
            out = moe_experts(x, comb, ex, l, act)
            return x + (0.1 * out).astype(x.dtype), out
        return jax.lax.scan(body, x, jnp.arange(L))

    def dense_layers(x, comb, ex):
        def body(x, p):
            out = dense_experts(x[:, None], comb[:, None], p, act)[:, 0]
            return x + (0.1 * out).astype(x.dtype), out
        return jax.lax.scan(body, x, ex)

    def a_layer_us(fn, *args):
        jax.block_until_ready(fn(*args))
        t1 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t1) / 10 / L * 1e6

    try:
        ex = seeded_experts(jax.random.PRNGKey(0), L, E, D, F)
        x64 = jax.random.normal(jax.random.PRNGKey(1), (64, D), jnp.bfloat16)
        kernel, dense = jax.jit(kernel_layers), jax.jit(dense_layers)
        compiled = kernel.lower(x64, gates(64, cell), ex).compile()
        hlo = compiled.as_text()
        rec["hlo_has"] = {w: w in hlo for w in want}
        rec["expert_moves"] = expert_moves(hlo, ex)
        _, out = compiled(x64, gates(64, cell), ex)
        _, ref = dense(x64, gates(64, cell), ex)
        out, ref = (np.asarray(a, np.float32) for a in (out, ref))
        rec["max_err"] = round(float(np.max(
            np.abs(out - ref) / (1 + np.abs(ref)))), 5)
        rec["out_rms"] = round(float(np.sqrt(np.mean(ref ** 2))), 4)
        rec["compile_run_s"] = round(time.perf_counter() - t0, 2)
        rec["rows"] = {}
        for rows in (32, 64):
            x = x64[:rows]
            at = {"dense_us_a_layer": round(
                a_layer_us(dense, x, gates(rows, E), ex), 1)}
            at["dense_gb_s"] = round(
                E * 3 * D * F / at["dense_us_a_layer"] / 1e3, 1)
            for label, touched in (("all", E), ("cell", cell), ("one", 1)):
                us = a_layer_us(kernel, x, gates(rows, touched), ex)
                at[label] = {"touched": touched, "us_a_layer": round(us, 1),
                             "gb_s": round(touched * 3 * D * F / us / 1e3, 1)}
            rec["rows"][str(rows)] = at
        rec["ok"] = bool(np.isfinite(out).all() and rec["max_err"] < 3e-2
                         and not rec["expert_moves"]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: the cells' own geometries for ops/latent_attention.py (32 heads
#: against one cached row of 576 values in 640 lanes, 512 of them the
#: values; page 16, a window of 256): name -> slots, layers, pages a
#: slot, the contexts the timed step draws (low, high) and the context
#: it then runs flat
LATENT_CELLS = {
    "latent": (96, 6, 512, (3100, 4200), 3100),
    "latent_rollout": (128, 10, 144, (64, 2304), 1290),
}


def run_latent(name, small, want):
    """ops/latent_attention.py at a cell's own geometry (LATENT_CELLS:
    `joyai48b.longthink`'s 96 slots over a pool of 49,153 pages in six
    layers, 6 GB; `xing29b.rollout`'s 128 slots of 144 pages in ten).
    The kernel against `jnp` over the same pages at contexts from
    nothing to the whole table that end inside a page, a dead slot
    first; then its time for the layers of one step, at contexts drawn
    over the cell's range and at one flat context, a call's microseconds
    beside the bytes the model's count gives the rows
    (servebench/latent_peaks.py): what `latent_attn_roofline` will read
    in the cell."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.ops.latent_attention import latent_attention

    rec = {"name": name, "ok": False}
    Nq, Rp, R, rank, page, W = 32, 640, 576, 512, 16, 256
    S, L, mp, drawn, flat = LATENT_CELLS[name]
    if small:
        S, Nq, L, mp, W, drawn, flat = 4, 4, 2, 8, 16, (5, 100), 60
    P = S * mp + 1
    t0 = time.perf_counter()
    try:
        keys = jax.random.split(jax.random.PRNGKey(7), 4)
        lanes = (jnp.arange(Rp) < R).astype(jnp.bfloat16)
        pool = jax.jit(lambda k: jax.random.normal(
            k, (L, P, 1, page, Rp), jnp.bfloat16) * lanes)(keys[0])
        q = jax.random.normal(keys[1], (S, Nq, Rp), jnp.bfloat16) * lanes
        win = jax.jit(lambda k: jax.random.normal(
            k, (L, S, 1, W, Rp), jnp.bfloat16) * lanes)(keys[2])
        rs = np.random.RandomState(3)
        table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp), jnp.int32)
        lens = rs.randint(1, mp * page - W, S)
        lens[0], lens[1], lens[2] = 0, 5, mp * page - W
        wc = rs.randint(1, W + 1, S)
        wc[0] = 0
        lens, wc = jnp.asarray(lens, jnp.int32), jnp.asarray(wc, jnp.int32)
        scale = 192 ** -0.5
        fn = jax.jit(lambda q, pool, ly, t, n, w, c: latent_attention(
            q, pool, ly, t, n, w, c, rank=rank, scale=scale))
        compiled = fn.lower(q, pool, 3 % L, table, lens, win, wc).compile()
        hlo = compiled.as_text()
        out = jax.block_until_ready(compiled(q, pool, 3 % L, table, lens,
                                             win, wc))
        rec["compile_run_s"] = round(time.perf_counter() - t0, 2)
        rec["hlo_has"] = {w: w in hlo for w in want}

        @jax.jit
        def ref(q, pool, ly, t, n, w, c):
            # slot by slot: the gathered view of 96 tables is 1 GB
            def one(args):
                qs, ts, ns, ws, cs = args
                rows = jnp.concatenate(
                    [pool[ly, ts, 0].reshape(mp * page, Rp), ws[0]])
                # (ws: the slot's entries of the window's layer)
                live = jnp.concatenate([jnp.arange(mp * page) < ns,
                                        jnp.arange(W) < cs])
                s = jnp.einsum("nr,cr->nc", qs, rows,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(live, s, -1e30), -1) * live
                return jnp.einsum("nc,cr->nr", p.astype(rows.dtype),
                                  rows[:, :rank],
                                  preferred_element_type=jnp.float32)
            return jax.lax.map(one, (q, t, n, w[ly], c))

        want_out = ref(q, pool, 3 % L, table, lens, win, wc)
        err = np.max(np.abs(np.asarray(out, np.float32)
                            - np.asarray(want_out)) /
                     (1 + np.abs(np.asarray(want_out))))
        rec["max_err"] = round(float(err), 5)
        rec["dead_slot_zero"] = not np.asarray(out[0], np.float32).any()
        # one step's layers, at the cell's drawn contexts and at one
        step = jax.jit(lambda q, pool, t, n, w, c: sum(
            latent_attention(q, pool, ly, t, n, w, c, rank=rank,
                             scale=scale).astype(jnp.float32).sum()
            for ly in range(L)))
        one = jnp.ones((S,), jnp.int32)

        def timed(ctx):
            """(ms a step, the rows' GB, their share of 819 GB/s)"""
            ctx = jnp.asarray(np.minimum(ctx, mp * page - W), jnp.int32)
            jax.block_until_ready(step(q, pool, table, ctx, win, one))
            t1 = time.perf_counter()
            for _ in range(10):
                r = step(q, pool, table, ctx, win, one)
            jax.block_until_ready(r)
            ms = (time.perf_counter() - t1) / 10 * 1e3
            rows_bytes = float(L) * float(ctx.sum()) * R * 2
            return (round(ms, 3), round(rows_bytes / 1e9, 3),
                    round(100 * rows_bytes / 819e9 / (ms / 1e3), 1))

        ms, _, share = timed(rs.randint(drawn[0], drawn[1] + 1, S))
        rec["drawn_call_us"] = round(ms * 1e3 / L, 1)
        rec["drawn_share_of_819_gb_s"] = share
        rec["step_ms"], rec["rows_gb"], rec["share_of_819_gb_s"] = timed(
            np.full(S, flat))
        rec["flat_call_us"] = round(rec["step_ms"] * 1e3 / L, 1)
        rec["ok"] = bool(np.isfinite(err) and err < 3e-2
                         and rec["dead_slot_zero"]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: the cells' own geometries for ops/paged_attention.py (32 query heads
#: over 8 KV heads unless said; page 16, head 128, a window of 256 of
#: which a decode row has staged 1-8):
#: name -> slots, Nq, Kv, int8 pool, layers, pages a slot, contexts
#: (low, high), the layer's sliding window or None
PAGED_CELLS = {
    "paged_cell_int8": (32, 32, 8, True, 8, 128, (170, 170), None),
    "paged_cell_bf16": (32, 28, 4, False, 8, 128, (170, 170), 4096),
    "paged_cell_tp4": (32, 8, 2, False, 8, 128, (170, 170), None),
    "paged_cell_rollout": (128, 32, 8, False, 1, 144, (64, 2304), None),
}


def run_paged_cell(name, small, want):
    """ops/paged_attention.py at a cell's own geometry (PAGED_CELLS:
    `mistral7b.batch`'s int8 pool, SmallThinker's 28 heads over 4 with
    the sliding scalar live, a tensor=4 shard's 8 over 2, granite's 128
    slots at lengths 64-2,304): the seconds it took to trace and lower
    and to compile, the kernel against `jnp` over the same pages, then
    its time a call beside the bytes of the rows it must read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.models.common import quantize_kv
    from butterfly_tpu.ops.paged_attention import paged_attention
    from butterfly_tpu.ops.window_stage import window_step

    rec = {"name": name, "ok": False}
    S, Nq, Kv, quant, L, mp, (lo, hi), sw = PAGED_CELLS[name]
    H, page, W = 128, 16, 256
    if small:
        S, L, mp, lo, hi, W = 4, 2, 8, min(lo, 100), min(hi, 120), 8
    P = S * mp + 1
    try:
        keys = jax.random.split(jax.random.PRNGKey(11), 5)
        bf = jnp.bfloat16

        @jax.jit
        def pools(k):
            x = jax.random.normal(k, (L, P, Kv, page, H), bf)
            if not quant:
                return x, None
            codes, scales = quantize_kv(jnp.moveaxis(x, 2, 3))
            return (jnp.moveaxis(codes, 2, 3),
                    jnp.moveaxis(scales, 2, 3).reshape(L, P, Kv * page))

        (kp, ksp), (vp, vsp) = pools(keys[0]), pools(keys[1])
        q = jax.random.normal(keys[2], (S, Nq, H), bf)
        # the window whole, as the serving path hands it: every layer's
        wk, wv = (jax.random.normal(k, (L, S, Kv, W, H), bf)
                  for k in keys[3:])
        wks, win_scale = None, 1.0
        if quant:   # codes of one common scale
            win_scale = 0.025
            wk, wv = ((w / win_scale).astype(jnp.int8) for w in (wk, wv))
            ws = window_step(W)
            wks = jnp.full((L, S, W // ws, Kv * ws), win_scale, jnp.float32)
        rs = np.random.RandomState(5)
        table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp), jnp.int32)
        lens = rs.randint(lo, hi + 1, S)
        wc = rs.randint(1, 9, S)
        if lo != hi:
            lens[0], wc[0], lens[1] = 0, 0, hi
        lens, wc = jnp.asarray(lens, jnp.int32), jnp.asarray(wc, jnp.int32)
        ly = 1 % L

        def call(q, kp, vp, ksp, vsp, ly, t, n, wk, wv, c, wks):
            return paged_attention(
                q, kp, vp, ly, t, n, ksp, vsp, win_k=wk, win_v=wv,
                win_count=c, win_k_scale=wks, win_v_scale=wks,
                sliding_window=sw)

        args = (q, kp, vp, ksp, vsp, ly, table, lens, wk, wv, wc, wks)
        t0 = time.perf_counter()
        lowered = jax.jit(call).lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["trace_lower_s"] = round(t1 - t0, 3)
        rec["compile_s"] = round(time.perf_counter() - t1, 3)
        rec["hlo_has"] = {w: w in compiled.as_text() for w in want}
        out = jax.block_until_ready(compiled(*args))

        @jax.jit
        def ref(q, kp, vp, ksp, vsp, ly, t, n, wk, wv, c, wks):
            f32 = jnp.float32
            G = Nq // Kv

            def one(a):
                qs, ts, ns, wk_s, wv_s, cs = a

                def rows(pool, sc, w):
                    x = pool[ly, ts].astype(f32)        # [mp, Kv, page, H]
                    if quant:
                        x = x * sc[ly, ts].reshape(mp, Kv, page, 1)
                    x = jnp.moveaxis(x, 1, 0).reshape(Kv, mp * page, H)
                    return jnp.concatenate(
                        [x, w.astype(f32) * win_scale], 1)

                k, v = rows(kp, ksp, wk_s), rows(vp, vsp, wv_s)
                pos = jnp.concatenate([jnp.arange(mp * page),
                                       ns + jnp.arange(W)])
                live = jnp.concatenate([jnp.arange(mp * page) < ns,
                                        jnp.arange(W) < cs])
                if sw:
                    live &= pos >= ns + cs - sw
                s = jnp.einsum("kgh,kch->kgc", qs.astype(f32).reshape(
                    Kv, G, H), k, precision="highest") * H ** -0.5
                p = jax.nn.softmax(jnp.where(live, s, -1e30), -1) * live
                return jnp.einsum("kgc,kch->kgh", p, v,
                                  precision="highest").reshape(Nq, H)
            return jax.lax.map(one, (q, t, n, wk[ly], wv[ly], c))

        want_out = ref(*args)
        err = np.max(np.abs(np.asarray(out, np.float32)
                            - np.asarray(want_out)) /
                     (1 + np.abs(np.asarray(want_out))))
        rec["max_err"] = round(float(err), 5)
        if lo != hi:
            rec["dead_slot_zero"] = not np.asarray(out[0], np.float32).any()
        # a step's L layers, as the serving program calls them
        step = jax.jit(lambda *a: sum(
            call(*a[:5], l, *a[6:]).astype(jnp.float32).sum()
            for l in range(L)))
        jax.block_until_ready(step(*args))
        t2 = time.perf_counter()
        for _ in range(20):
            r = step(*args)
        jax.block_until_ready(r)
        rec["call_us"] = round((time.perf_counter() - t2) / 20 / L * 1e6, 1)
        row = 2 * Kv * H * (1 if quant else 2) + (8 * Kv if quant else 0)
        rows_bytes = float(jnp.sum(lens)) * row
        rec["rows_mb"] = round(rows_bytes / 1e6, 2)
        rec["share_of_819_gb_s"] = round(
            100 * rows_bytes / 819e9 / (rec["call_us"] / 1e6), 1)
        rec["ok"] = bool(np.isfinite(err) and err < 3e-2
                         and rec.get("dead_slot_zero", True)
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: `keye30b.think`'s geometry for ops/sparse_attention.py (32 queries
#: over 4 KV heads of 128, token-major pages of 16 in eight layers, index
#: keys of 64 under 16 index heads, a window of 256 of which a decode
#: row has staged 1-4): slots, pages a slot (a table of 7,168), topk,
#: and the contexts every slot then holds flat beside the drawn ones
SPARSE_CELL = (32, 448, 2048, (512, 7168))


def run_sparse_cell(name, small, want):
    """A decode row's read of a model with an indexer at `keye30b.think`'s
    geometry (SPARSE_CELL), both ways cache/paged.py sparse_paged_attend
    moves the rows: the Pallas kernel over the slot's live pages with
    the selection as a mask (ops/sparse_attention.py) and the gather of
    the selected rows, on the same operands. Their outputs against each
    other (a dead slot among them), then microseconds a call over one
    step's layers: the kernel ALONE under a selection of topk of each
    slot's live rows, and each branch whole (index scores, selection and
    read), at contexts drawn as `think` draws them (a prompt log-uniform
    over 1,024-4,096 and 0-3,072 answered) and at 512 and 7,168 in every
    slot: the ends of the table, which say where the masked read stops
    paying (MASKED_READ_SPAN: the kernel pays by the LIVE row, the
    gather by the selected one)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.paged import KVWindow, sparse_paged_attend
    from butterfly_tpu.core.config import tiny
    from butterfly_tpu.ops.sparse_attention import sparse_attention

    rec = {"name": name, "ok": False}
    S, mp, topk, flats = SPARSE_CELL
    L, Nq, Kv, H, page, Hi, Ni, W = 8, 32, 4, 128, 16, 64, 16, 256
    if small:
        S, mp, topk, flats, L, Nq, Kv, H, page, Hi, Ni, W = (
            4, 48, 48, (30, 192), 2, 4, 2, 8, 4, 6, 2, 8)
    P, S_max = S * mp + 1, mp * page
    cfg = tiny("keye", num_heads=Nq, num_kv_heads=Kv, head_dim=H,
               index_heads=Ni, index_head_dim=Hi, index_topk=topk)
    try:
        keys = jax.random.split(jax.random.PRNGKey(51), 9)
        bf, f32 = jnp.bfloat16, jnp.float32

        def rnd(k, shape, dt=bf):
            return jax.jit(lambda k: jax.random.normal(k, shape, dt))(k)

        kp, vp = (rnd(k, (L, P, 1, page, Kv * H)) for k in keys[:2])
        kip = rnd(keys[2], (L, P, 1, page, Hi))
        window = KVWindow(k=rnd(keys[3], (L, S, 1, W, Kv * H)),
                          v=rnd(keys[4], (L, S, 1, W, Kv * H)),
                          ki=rnd(keys[5], (L, S, 1, W, Hi)))
        q = rnd(keys[6], (S, 1, Nq, H))
        qi, w = rnd(keys[7], (S, 1, Ni, Hi), f32), rnd(keys[8], (S, 1, Ni),
                                                       f32)
        rs = np.random.RandomState(51)
        table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp) + 1,
                            jnp.int32)
        drawn = np.minimum(S_max, np.exp(rs.uniform(
            np.log(S_max / 7), np.log(S_max * 4 / 7), S)).astype(int)
            + rs.randint(0, S_max * 3 // 7 + 1, S))

        def operands(ctx):
            """A decode row a slot at position ctx - 1 (0: a dead slot),
            1-4 rows staged before it."""
            ctx = np.asarray(ctx)
            pos = jnp.asarray(np.maximum(ctx - 1, 0), jnp.int32)
            staged = jnp.asarray(np.minimum(rs.randint(1, 5, S),
                                            np.maximum(ctx - 1, 0)), jnp.int32)
            return pos, jnp.asarray(ctx > 0), staged

        def branch(use_kernel, layers):
            def run(q, qi, w, kp, vp, kip, table, window, pos, active,
                    staged):
                mask = active[:, None, None] & (
                    jnp.arange(S_max)[None, None, :] <= pos[:, None, None])
                return [sparse_paged_attend(
                    q, qi, w, kp, vp, kip, ly, cfg=cfg, page_table=table,
                    positions=pos[:, None], mask=mask, active=active,
                    use_kernel=use_kernel, win=(window, staged, None))
                    for ly in layers]
            return run

        fixed = (q, qi, w, kp, vp, kip, table, window)
        # agreement, a dead slot and the table's two ends among the slots
        ctx = drawn.copy()
        ctx[0], ctx[1], ctx[2] = 0, S_max, min(topk // 4, S_max)
        probe = operands(ctx)
        masked = jax.jit(branch(True, [3 % L])).lower(*fixed, *probe).compile()
        rec["hlo_has"] = {w_: w_ in masked.as_text() for w_ in want}
        (got, got_n), = masked(*fixed, *probe)
        (ref, ref_n), = jax.jit(branch(False, [3 % L]))(*fixed, *probe)
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        rec["max_err"] = round(float(np.max(
            np.abs(got[1:] - ref[1:]) / (1 + np.abs(ref[1:])))), 5)
        rec["dead_slot_zero"] = not got[0].any()
        # what was attended is what was attended; what moved is not
        rec["count_masked"] = np.asarray(got_n).tolist()
        rec["count_gather"] = np.asarray(ref_n).tolist()

        def timed(fn, *args, n=10):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(n):
                r = fn(*args)
            jax.block_until_ready(r)
            return round((time.perf_counter() - t0) / n / L * 1e6, 1)

        def step(use_kernel):
            run = branch(use_kernel, range(L))
            return jax.jit(lambda *a: sum(
                o.astype(f32).sum() for o, _ in run(*a)))

        alone = jax.jit(lambda q, kp, vp, table, lens, sel, wk, wv, wc: sum(
            sparse_attention(q, kp, vp, ly, table, lens, sel, wk, wv,
                             wc).astype(f32).sum() for ly in range(L)))
        steps = {True: step(True), False: step(False)}
        rec["contexts"] = {}
        for label, ctx in [("drawn", drawn)] + [
                (str(n), np.full(S, n)) for n in flats]:
            pos, active, staged = operands(ctx)
            live = jnp.arange(S_max)[None, :] <= pos[:, None]
            keep = jax.random.uniform(keys[0], (S, S_max)) \
                < topk / jnp.maximum(pos[:, None] + 1, topk)
            rows = int(np.sum(ctx))
            kernel_us = timed(alone, q[:, 0], kp, vp, table, pos - staged,
                              live & keep, window.k, window.v, staged + 1)
            rec["contexts"][label] = {
                "live_rows": rows,
                "selected_rows": int(np.minimum(ctx, topk).sum()),
                "kernel_alone_us": kernel_us,
                "kernel_ns_a_live_row": round(kernel_us * 1e3 / rows, 2),
                "kernel_share_of_819_gb_s": round(
                    100 * rows * 4 * Kv * H / 819e9 / (kernel_us / 1e6), 1),
                "masked_branch_us": timed(steps[True], *fixed, pos, active,
                                          staged),
                "gather_branch_us": timed(steps[False], *fixed, pos, active,
                                          staged)}
        rec["ok"] = bool(np.isfinite(rec["max_err"]) and rec["max_err"] < 3e-2
                         and rec["dead_slot_zero"]
                         and rec["count_masked"][:3] == rec["count_gather"][:3]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: the two indexers ops/index_scores.py serves, at their cells' geometry
#: (SPARSE_CELL's slots and table, pages of 16 in eight layers, a window
#: of 256): name -> index heads, the width of an index key, and which
#: way the view's path takes the window's keys (cache/paged.py
#: _index_selection's `scatter`)
INDEX_CELLS = {
    "index_cell_keye": (16, 64, False),
    "index_cell_glm5": (32, 128, True),
}


def run_index_cell(name, small, want):
    """A decode row's index scores at a selecting cell's geometry
    (INDEX_CELLS), both ways cache/paged.py _index_selection makes them,
    on the same operands: the Pallas walk of the slot's live index-key
    pages (ops/index_scores.py) and the table's keys gathered to a view
    and scored by XLA. Their scores against each other at the positions
    a row may attend (against the summands' size: XLA's product at
    HIGHEST is itself six bfloat16 passes on the chip, no float64) and
    the two selections as masks (a dead slot and
    the table's two ends among the slots), then microseconds a call
    over one step's layers, nanoseconds a live page and the model's
    bytes (a live position's index_head_dim bfloat16) against 819 GB/s,
    at contexts drawn as `think` draws them and flat at 512, the cell's
    mean and 7,168 in every slot."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.paged import (LANES, KVWindow,
                                           _index_selection)
    from butterfly_tpu.models.common import select_mask
    from butterfly_tpu.ops.index_scores import index_scores

    rec = {"name": name, "ok": False}
    S, mp, topk, flats = SPARSE_CELL
    Ni, Hi, scatter = INDEX_CELLS[name]
    L, page, W, flats = 8, 16, 256, (flats[0], 3584, flats[1])
    if small:
        S, mp, topk, flats, L, page, Ni, Hi, W = (
            4, 48, 48, (30, 192), 2, 4, 2, Hi // 8, 8)
    P, S_max, width = S * mp + 1, mp * page, -(-Hi // LANES) * LANES
    try:
        keys = jax.random.split(jax.random.PRNGKey(53), 4)
        bf, f32 = jnp.bfloat16, jnp.float32

        def cached(k, shape):
            """Index keys as the pool holds them: zeros behind Hi."""
            return jax.jit(lambda k: jnp.pad(
                jax.random.normal(k, (*shape, Hi), bf),
                [(0, 0)] * len(shape) + [(0, width - Hi)]))(k)

        kip = cached(keys[0], (L, P, 1, page))
        window = KVWindow(k=jnp.zeros((L, S, 1, W, LANES), bf), v=None,
                          ki=cached(keys[1], (L, S, 1, W)))
        qi = jax.random.normal(keys[2], (S, 1, Ni, Hi), f32)
        w = jax.random.normal(keys[3], (S, 1, Ni), f32)
        rs = np.random.RandomState(53)
        table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp) + 1,
                            jnp.int32)
        drawn = np.minimum(S_max, np.exp(rs.uniform(
            np.log(S_max / 7), np.log(S_max * 4 / 7), S)).astype(int)
            + rs.randint(0, S_max * 3 // 7 + 1, S))

        def operands(ctx):
            """A decode row a slot at position ctx - 1 (0: a dead slot),
            1-4 rows staged before it."""
            ctx = np.asarray(ctx)
            pos = jnp.asarray(np.maximum(ctx - 1, 0), jnp.int32)
            staged = jnp.asarray(np.minimum(rs.randint(1, 5, S),
                                            np.maximum(ctx - 1, 0)), jnp.int32)
            return pos, jnp.asarray(ctx > 0), staged

        def branch(use_kernel, layers, size=False):
            def run(qi, w, kip, table, window, pos, active, staged):
                mask = active[:, None, None] & (
                    jnp.arange(S_max)[None, None, :] <= pos[:, None, None])
                return mask, [_index_selection(
                    (qi, jnp.abs(w) if size else w, kip),
                    (window, staged, None), ly,
                    page_table=table, positions=pos[:, None], mask=mask,
                    active=active, topk=topk, select="index",
                    scatter=scatter, use_kernel=use_kernel)[0]
                    for ly in layers]
            return run

        fixed = (qi, w, kip, table, window)
        ctx = drawn.copy()
        ctx[0], ctx[1], ctx[2] = 0, S_max, min(topk // 4, S_max)
        probe = operands(ctx)
        walked = jax.jit(branch(True, [3 % L])).lower(*fixed, *probe).compile()
        rec["hlo_has"] = {w_: w_ in walked.as_text() for w_ in want}
        mask, (got,) = walked(*fixed, *probe)
        _, (ref,) = jax.jit(branch(False, [3 % L]))(*fixed, *probe)
        # rounding is of the summands, relu(s) w a head, whose signs
        # cancel in a score: the same sum under |w| is their size
        _, (size,) = jax.jit(branch(False, [3 % L], True))(*fixed, *probe)
        picked = [np.asarray(select_mask(a[:, 0], mask[:, 0], topk))
                  for a in (got, ref)]
        got, ref, size, mask = (np.asarray(a) for a in (got, ref, size, mask))
        rec["max_err"] = float(np.max(
            np.where(mask, np.abs(got - ref) / (1 + size), 0)))
        rec["finite"] = bool(np.isfinite(got).all())
        rec["selections_differ_at"] = int((picked[0] != picked[1]).sum())

        def timed(fn, *args, n=10):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(n):
                r = fn(*args)
            jax.block_until_ready(r)
            return round((time.perf_counter() - t0) / n / L * 1e6, 1)

        def step(use_kernel):
            run = branch(use_kernel, range(L))
            return jax.jit(lambda *a: sum(o.sum() for o in run(*a)[1]))

        alone = jax.jit(lambda qi, w, kip, table, lens, wki: sum(
            index_scores(qi[:, 0], w[:, 0], kip, ly, table, lens, wki).sum()
            for ly in range(L)))
        steps = {True: step(True), False: step(False)}
        rec["contexts"] = {}
        for label, ctx in [("drawn", drawn)] + [
                (str(n), np.full(S, n)) for n in flats]:
            pos, active, staged = operands(ctx)
            rows = int(np.sum(ctx))
            pages = int(np.sum(-(-np.asarray(pos - staged) // page)))
            us = timed(alone, qi, w, kip, table, pos - staged, window.ki)
            rec["contexts"][label] = {
                "live_rows": rows, "live_pages": pages,
                "kernel_alone_us": us,
                "kernel_ns_a_live_page": round(us * 1e3 / max(pages, 1), 2),
                "kernel_share_of_819_gb_s": round(
                    100 * rows * 2 * Hi / 819e9 / (us / 1e6), 1),
                "walk_branch_us": timed(steps[True], *fixed, pos, active,
                                        staged),
                "view_branch_us": timed(steps[False], *fixed, pos, active,
                                        staged)}
        rec["ok"] = bool(rec["finite"] and rec["max_err"] < 5e-6
                         and rec["selections_differ_at"] == 0
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: the arrays of index scores the selecting cells' programs select over
#: (SPARSE_CELL's slots, table and topk): Keye's decode rows, a chunk's
#: 32 rows of its one slot, GLM-5's decode rows
SELECT_CELLS = {
    "select_cell_keye": lambda S, n: (S, n),
    "select_cell_chunk": lambda S, n: (1, S, n),
    "select_cell_glm5": lambda S, n: (S, 1, n),
}


def run_select_cell(name, small, want):
    """A selection at a selecting cell's geometry (SELECT_CELLS), both
    ways cache/paged.py _selection makes it, on the same scores: by
    COUNTING in the Pallas call (ops/select_mask.py) and by lax.top_k
    and a running count (models/common.py select_mask). The two masks
    against each other (rows of distinct scores, rows of a few levels
    that tie astride the cut, -inf among the valid, a dead slot and a
    full table among the rows), what each program still sorts
    (span_sorts), then microseconds a call over one step's layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.cache.paged import _selection

    rec = {"name": name, "ok": False}
    S, mp, topk, _ = SPARSE_CELL
    L, page = 8, 16
    if small:
        S, mp, topk, L = 8, 16, 48, 2
    n = mp * page
    shape = SELECT_CELLS[name](S, n)
    try:
        rs = np.random.RandomState(55)
        ctx = np.minimum(n, np.exp(rs.uniform(
            np.log(n / 7), np.log(n * 4 / 7), S)).astype(int)
            + rs.randint(0, n * 3 // 7 + 1, S))
        ctx[0], ctx[1] = 0, n
        valid = jnp.asarray((np.arange(n)[None] < ctx[:, None])
                            .reshape(shape))
        scores = jax.random.normal(jax.random.PRNGKey(55), (L, S, n),
                                   jnp.float32)
        levels = jnp.round(scores * 4) / 4
        scores = scores.at[:, 1::4].set(levels[:, 1::4])
        scores = scores.at[:, 2::4].set(jnp.where(
            levels[:, 2::4] < 0, -jnp.inf, levels[:, 2::4]))
        scores = scores.reshape(L, *shape)

        def branch(use_kernel):
            return jax.jit(lambda scores, valid: [
                _selection(scores[ly], valid, topk, use_kernel) != 0
                for ly in range(L)])

        def step(use_kernel):
            return jax.jit(lambda scores, valid: sum(
                _selection(scores[ly], valid, topk, use_kernel).sum()
                for ly in range(L)))

        counted = branch(True).lower(scores, valid).compile()
        plain = branch(False).lower(scores, valid).compile()
        rec["hlo_has"] = {w_: w_ in counted.as_text() for w_ in want}
        rec["sorts"] = {"counted": span_sorts(counted.as_text(), n),
                        "plain": span_sorts(plain.as_text(), n)}
        got, ref = (np.stack([np.asarray(m) for m in fn(scores, valid)])
                    for fn in (counted, plain))
        rec["selected"] = int(ref.sum())
        rec["selections_differ_at"] = int((got != ref).sum())
        rec["max_err"] = float(rec["selections_differ_at"] > 0)

        def timed(fn, reps=30):
            jax.block_until_ready(fn(scores, valid))
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(scores, valid)
            jax.block_until_ready(r)
            return round((time.perf_counter() - t0) / reps / L * 1e6, 1)

        rec["counted_us"] = timed(step(True))
        rec["plain_us"] = timed(step(False))
        rec["ok"] = bool(rec["selections_differ_at"] == 0
                         and rec["selected"] > 0
                         and not rec["sorts"]["counted"]
                         and rec["sorts"]["plain"]
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


#: the cells' windows for ops/window_stage.py (W 256, a chunk of 32
#: columns beside the decode rows): name -> layers, slots, the row
#: leaves' (heads, width) and dtype, int8 scale leaves too? (Keye's
#: window, three leaves since its index keys lie in whole lanes, PR 53,
#: has no case here yet.)
STAGE_CELLS = {
    "stage_cell_int8": (32, 32, ((8, 128),) * 2, "int8", True),
    "stage_cell_bf16": (16, 32, ((4, 128),) * 2, "bfloat16", False),
    "stage_cell_latent": (6, 96, ((1, 640),), "bfloat16", False),
    "stage_cell_rollout": (2, 128, ((8, 128),) * 2, "bfloat16", False),
}


def run_stage_cell(name, small, want):
    """ops/window_stage.py at a cell's own window (STAGE_CELLS): one
    step's rows (a decode row a live slot, a fifth of the slots dead,
    one chunk of 32 columns astride two groups) staged into every layer
    in turn, the leaves donated as the serving block donates them;
    against XLA's scatter of the same rows, bit for bit over the whole
    leaves, then its time a call (a layer-step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.ops.window_stage import stage_window, window_step

    rec = {"name": name, "ok": False}
    L, S, rows_of, dtype, quant = STAGE_CELLS[name]
    W, C = 256, 32
    if small:
        L, S, W, C = 2, 4, 64, 8
    dt, gw = jnp.dtype(dtype), window_step(W)
    try:
        keys = iter(jax.random.split(jax.random.PRNGKey(13), 16))

        def rnd(shape, dt):
            if dt == jnp.int8:
                return jax.random.randint(next(keys), shape, -127, 128,
                                          jnp.int8)
            return jax.random.normal(next(keys), shape, jnp.float32
                                     ).astype(dt)

        leaves = tuple(rnd((L, S, kv, W, h), dt) for kv, h in rows_of)
        Kv = rows_of[0][0]
        scales = tuple(rnd((L, S, W // gw, Kv * gw), jnp.dtype("float32"))
                       for _ in range(2 * quant))
        N = S + C
        fresh = tuple(rnd((N, kv, h), dt) for kv, h in rows_of)
        fresh_s = tuple(rnd((N, Kv), jnp.dtype("float32")) for _ in scales)
        rs = np.random.RandomState(9)
        count = rs.randint(0, W - 2 * C, S)
        active = rs.rand(S) > 0.2
        active[1], count[1] = False, gw - 3      # slot 1 takes the chunk
        count[2], count[3] = W - 1, W            # the last entry; a full one
        runs = (jnp.asarray(np.stack([np.arange(S), count,
                                      active & (count < W)]), jnp.int32),
                jnp.asarray([[1], [count[1]], [C - 3]], jnp.int32))
        slot = np.concatenate([np.arange(S), np.full(C, 1)])
        idx = np.concatenate([np.where(active, count, W),
                              np.where(np.arange(C) < C - 3,
                                       count[1] + np.arange(C), W)])

        def stage(leaves, scales, ly):
            return stage_window(leaves, scales, fresh, fresh_s, ly, runs,
                                widths=(1, C), looped=False)

        t0 = time.perf_counter()
        # (one call alone is no loop: `looped` False, as a run of one
        # layer says it; the scan below is the serving programs' case)
        lowered = jax.jit(stage).lower(leaves, scales, 1 % L)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["trace_lower_s"] = round(t1 - t0, 3)
        rec["compile_s"] = round(time.perf_counter() - t1, 3)
        rec["hlo_has"] = {w: w in compiled.as_text() for w in want}

        @jax.jit
        def ref(leaves, scales, ly):
            cols = jnp.arange(Kv)[None] * gw + (idx % gw)[:, None]
            return (tuple(a.at[ly, slot, :, idx].set(r, mode="drop")
                          for a, r in zip(leaves, fresh)),
                    tuple(a.at[ly, slot[:, None], (idx // gw)[:, None],
                               cols].set(r, mode="drop")
                          for a, r in zip(scales, fresh_s)))

        want_out = jax.block_until_ready(ref(leaves, scales, 1 % L))
        out = jax.block_until_ready(compiled(leaves, scales, 1 % L))
        rec["bytes_differ"] = int(sum(
            np.count_nonzero(np.asarray(a).view(np.uint8)
                             != np.asarray(b).view(np.uint8))
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want_out))))
        rec["max_err"] = float(rec["bytes_differ"] > 0)

        # a step's L layers in turn, the leaves carried and donated
        def step(leaves, scales):
            def layer(held, ly):
                return stage_window(*held, fresh, fresh_s, ly, runs,
                                    widths=(1, C)), None
            return jax.lax.scan(layer, (leaves, scales), jnp.arange(L))[0]

        step = jax.jit(step, donate_argnums=(0, 1))
        held = jax.block_until_ready(step(*out))
        t2 = time.perf_counter()
        for _ in range(20):
            held = step(*held)
        jax.block_until_ready(held)
        rec["call_us"] = round((time.perf_counter() - t2) / 20 / L * 1e6, 1)
        row = sum(kv * h for kv, h in rows_of) * dt.itemsize
        rec["rows_mb"] = round((int(active.sum()) + C - 3) * row / 1e6, 3)
        rec["share_of_819_gb_s"] = round(
            100 * rec["rows_mb"] * 1e6 / 819e9 / (rec["call_us"] / 1e6), 2)
        rec["ok"] = bool(rec["bytes_differ"] == 0
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


def run_case(name, fn, ref, args, mesh=None, want=(MOSAIC_CALL,)):
    """Compile `fn`, look for `want` in its HLO, run it, compare."""
    import jax
    import numpy as np

    from butterfly_tpu.core.mesh import mesh_ctx

    rec = {"name": name, "ok": False}
    t0 = time.perf_counter()
    try:
        with mesh_ctx(mesh):
            compiled = jax.jit(fn).lower(*args).compile()
            hlo = compiled.as_text()
            out = jax.block_until_ready(compiled(*args))
        rec["compile_run_s"] = round(time.perf_counter() - t0, 2)
        rec["hlo_has"] = {w: w in hlo for w in want}
        with jax.default_matmul_precision("highest"):
            want_out = jax.jit(ref)(*args)
        # |a - b| / (1 + |b|): absolute near zero, relative for the
        # ring's unnormalized sums (and 0 on its -1e30 "masked" maxima)
        errs = [float(np.max(np.abs(a - b) / (1 + np.abs(b))))
                for a, b in zip(
                    (np.asarray(x, np.float32) for x in jax.tree.leaves(out)),
                    (np.asarray(x, np.float32)
                     for x in jax.tree.leaves(want_out)))]
        rec["max_err"] = round(max(errs), 5)
        finite = all(np.isfinite(np.asarray(a, np.float32)).all()
                     for a in jax.tree.leaves(out))
        # same bf16 operands on both sides; the kernel's f32 dots may
        # run at the MXU's default precision, the reference at highest
        rec["ok"] = bool(finite and max(errs) < 3e-2
                         and all(rec["hlo_has"].values()))
    except Exception as e:  # a compiler refusal is the finding: record it
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--windows", default="256,16",
                    help="window widths to check: inflight_blocks x "
                         "decode_steps_per_tick x chunk width; 256 is "
                         "`serve --decode-steps-per-tick 4` (chip_smoke), "
                         "16 is two blocks of 8 steps with no chunk")
    ap.add_argument("--small", action="store_true",
                    help="cut shapes so an interpreted CPU run finishes "
                         "(debugging aid; such a run still exits 1)")
    ap.add_argument("--only", default="",
                    help="run only the cases whose name contains this "
                         "(e.g. '@' = the mesh cases, already proven alone)")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    ap.add_argument("--cell", default=None, metavar="CONFIG.json",
                    help="compile that configuration's mixed and decode "
                         "block for a DESCRIBED v5e and print what they "
                         "hold and move (cell_blocks); needs no chip, and "
                         "nothing else runs")
    ap.add_argument("--hlo-dir", default=None,
                    help="with --cell: write each block's compiled text here")
    args = ap.parse_args()
    if args.cell:
        recs = cell_blocks(json.loads(Path(args.cell).read_text()),
                           args.hlo_dir)
        line = json.dumps({"ok": all(r["ok"] for r in recs), "blocks": recs})
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(line + "\n")
        print(line)
        return 0 if all(r["ok"] for r in recs) else 1

    import jax

    from butterfly_tpu.core.compile_cache import place_compile_cache
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.ops import kernel_mode

    place_compile_cache()
    devs = jax.devices()
    mode = kernel_mode(True)
    windows = [int(w) for w in args.windows.split(",") if w]
    cases = build_cases(args.small, windows)
    want = (MOSAIC_CALL,) if mode == "compiled" else ()
    def wanted(name):
        return args.only in name

    results = [run_case(n, k, r, a, want=want)
               for n, k, r, a, _ in cases if wanted(n)]
    if wanted("ssm_step"):
        results.append(run_ssm_step("ssm_step", args.small, want))
    if wanted("gdn_step"):
        results.append(run_gdn_step("gdn_step", args.small, want))
    if wanted("mamba1_step"):
        results.append(run_mamba1_step("mamba1_step", args.small, want))
    results += [run_latent(n, args.small, want)
                for n in LATENT_CELLS if wanted(n)]
    results += [run_paged_cell(n, args.small, want)
                for n in PAGED_CELLS if wanted(n)]
    results += [run_expert_cell(n, args.small, want)
                for n in EXPERT_CELLS if wanted(n)]
    results += [run_stage_cell(n, args.small, want)
                for n in STAGE_CELLS if wanted(n)]
    if wanted("sparse_cell"):
        results.append(run_sparse_cell("sparse_cell", args.small, want))
    results += [run_index_cell(n, args.small, want)
                for n in INDEX_CELLS if wanted(n)]
    results += [run_select_cell(n, args.small, want)
                for n in SELECT_CELLS if wanted(n)]
    if wanted("serve_block"):
        results.append(run_serving_block("serve_block", args.small, None,
                                         want))
    if len(devs) >= 4:
        mesh = make_mesh(MeshConfig(tensor=4), devs[:4])
        for n, _, r, a, sharded in cases:
            if sharded is not None and wanted(n + "@tp4"):
                results.append(run_case(n + "@tp4", sharded, r,
                                        tp_place(mesh, n, a), mesh, want))
        if wanted("ring@sp4"):
            smesh, fn, ref, a = ring_sharded_case(args.small)
            results.append(run_case("ring@sp4", fn, ref, a, smesh,
                                    want + ("collective-permute",)))
        if wanted("serve_block@tp4"):
            results.append(run_serving_block(
                "serve_block@tp4", args.small, mesh, want + ("all-reduce",)))
    for r in results:
        print(f"{'ok  ' if r['ok'] else 'FAIL'} {r['name']:<24}"
              f" err={r.get('max_err')} hlo={r.get('hlo_has')}"
              f" t={r.get('compile_run_s', r.get('compile_s'))}"
              + (f" step={r['step_ms']}ms rows={r['rows_gb']}GB "
                 f"({r['share_of_819_gb_s']}% of 819 GB/s)"
                 if "step_ms" in r else "")
              + (f" call={r['flat_call_us']}us flat, "
                 f"{r['drawn_call_us']}us drawn "
                 f"({r['drawn_share_of_819_gb_s']}%)"
                 if "flat_call_us" in r else "")
              + (f" call={r['call_us']}us rows={r['rows_mb']}MB "
                 f"({r['share_of_819_gb_s']}% of 819 GB/s) trace+lower="
                 f"{r['trace_lower_s']}s compile={r['compile_s']}s"
                 if "call_us" in r else "")
              + (f" calls={r['kernel_calls']}" if "kernel_calls" in r else "")
              + (f" a layer-step {r['kernel_us_a_layer_step']}us "
                 f"({r['kernel_gb_s']} GB/s of {r['layer_pass_mb']}MB), the "
                 f"jnp step {r['jnp_us_a_layer_step']}us "
                 f"({r['jnp_gb_s']} GB/s)"
                 if "jnp_us_a_layer_step" in r else "")
              + (f" counted={r['counted_us']}us a call, lax.top_k and the "
                 f"running count {r['plain_us']}us"
                 if "counted_us" in r else "")
              + "".join(
                  f"\n     {rows} rows: dense {at['dense_us_a_layer']}us a "
                  f"layer ({at['dense_gb_s']} GB/s)" + "".join(
                      f", {at[w]['touched']} touched {at[w]['us_a_layer']}us"
                      f" ({at[w]['gb_s']} GB/s)"
                      for w in ("all", "cell", "one"))
                  for rows, at in r.get("rows", {}).items()
                  if "dense_us_a_layer" in at)
              + "".join(
                  f"\n     {label}: the walk alone {c['kernel_alone_us']}us "
                  f"({c['kernel_ns_a_live_page']} ns a live page, "
                  f"{c['kernel_share_of_819_gb_s']}% of 819 GB/s), its "
                  f"branch {c['walk_branch_us']}us, the view's "
                  f"{c['view_branch_us']}us"
                  for label, c in r.get("contexts", {}).items()
                  if "walk_branch_us" in c)
              + "".join(
                  f"\n     {label}: kernel alone {c['kernel_alone_us']}us "
                  f"({c['kernel_ns_a_live_row']} ns a live row, "
                  f"{c['kernel_share_of_819_gb_s']}% of 819 GB/s), the "
                  f"masked branch {c['masked_branch_us']}us, the gather "
                  f"branch {c['gather_branch_us']}us"
                  for label, c in r.get("contexts", {}).items()
                  if "masked_branch_us" in c)
              + (f"\n     {r['error']}" if "error" in r else ""))
    ok = mode == "compiled" and all(r["ok"] for r in results)
    if mode != "compiled":
        print(f"chip_kernels: kernels were {mode}ed on platform "
              f"{devs[0].platform!r}, not compiled — this run says nothing "
              "about Mosaic", file=sys.stderr)
    summary = {"ok": ok, "kernels": mode,
               "device": {"platform": devs[0].platform,
                          "kind": devs[0].device_kind, "count": len(devs)},
               "passed": sum(r["ok"] for r in results),
               "failed": [r["name"] for r in results if not r["ok"]],
               "results": results}
    line = json.dumps(summary)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
