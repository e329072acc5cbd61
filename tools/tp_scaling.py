#!/usr/bin/env python
"""Tensor-parallel scaling model from compiled HLO (BASELINE.md metric:
"TP scaling efficiency 8 -> 64").

A MODEL, not a measurement: no mesh wider than four chips is at hand,
so the projection is built the way the scaling-book recipe says to
reason about it: lower the ACTUAL decode/prefill programs over
fake-device meshes of growing `tensor` size, read the collectives XLA
inserted out of the optimized HLO (op kind + operand shapes -> bytes
moved per step), and combine with the v5e roofline numbers
(HBM 819 GB/s, one-way ICI ~ 45 GB/s/link on the 2D torus) into a
per-chip step-time model:

    t(tp) = max(weight_bytes/tp / HBM_BW, flops/tp / PEAK) + comm(tp)/ICI
    eff(tp) = t(1-chip work split ideally) / (tp * t(tp))

Collective payloads measured at tp in {2,4,8} extrapolate to 16..64:
Megatron TP moves 2 all-reduces of the [B,1,D] activation per layer
per step regardless of tp (ring all-reduce: each chip sends/receives
2*(tp-1)/tp * payload), so per-chip comm bytes are ~constant while
per-chip compute shrinks 1/tp — exactly the regime the table shows.

Usage: python tools/tp_scaling.py [--layers 2] [--batch 8]
Prints the table (and writes it to --out when given). Its step times
are projections from published peaks, never device metrics.
"""
from __future__ import annotations

import argparse
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

HBM_BW = 819e9          # v5e usable HBM bytes/s
PEAK_FLOPS = 197e12     # v5e bf16 dense peak
ICI_BW = 45e9           # v5e one-way per-link ICI bytes/s (2D torus)

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
               "s32": 4, "u32": 4, "pred": 1, "f64": 8, "s64": 8}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-permute")


def collective_bytes(hlo: str) -> dict:
    """Sum output bytes per collective kind from optimized HLO text."""
    out = {k: 0 for k in COLLECTIVES}
    for line in hlo.splitlines():
        s = line.lstrip()
        if "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        if "-done" in lhs:      # async pairs: count the -start only
            continue
        kind = next((k for k in COLLECTIVES if k in lhs), None)
        if kind is None:
            continue
        m = re.match(r"\s*\(?([a-z0-9]+)\[([0-9,]*)\]", rhs)
        if not m:
            continue
        dt, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * DTYPE_BYTES.get(dt, 4)
    return out


def measure_subprocess(tp: int, layers: int, batch: int, seq: int):
    """Run measure() in a child process: the CPU device count must be
    set before the backend initializes, so each mesh size needs a fresh
    interpreter."""
    import json
    import subprocess
    r = subprocess.run(
        [sys.executable, __file__, "--measure-tp", str(tp),
         "--layers", str(layers), "--batch", str(batch),
         "--seq", str(seq)],
        capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"tp={tp} measurement failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.splitlines()[-1])


def measure(tp: int, layers: int, batch: int, seq: int):
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", max(tp, 1))
    import jax.numpy as jnp
    from butterfly_tpu.core.config import MeshConfig, llama3_8b
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.models.common import Model, forward, init_cache
    from butterfly_tpu.parallel.partition import (compiled_hlo, shard_cache,
                                                  shard_params)

    # Llama-3-8B LAYER geometry (the per-layer collectives are what
    # scale); a short stack keeps CPU compiles tractable and per-layer
    # numbers extrapolate exactly (collectives are per-layer identical).
    cfg = llama3_8b().replace(num_layers=layers, max_seq_len=seq,
                              dtype="float32", param_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(tensor=tp)) if tp > 1 else None
    if mesh is not None:
        params = shard_params(params, cfg, mesh)
    cache = init_cache(cfg, batch, seq)
    if mesh is not None:
        cache = shard_cache(cache, cfg, mesh)
    tok1 = jnp.zeros((batch, 1), jnp.int32)

    def decode(p, t, c):
        return forward(p, cfg, t, c)

    hlo = compiled_hlo(decode, params, tok1, cache, mesh=mesh)
    return collective_bytes(hlo)


def model_row(tp: int, per_layer_ar_bytes: float, cfg_layers: int = 32,
              batch: int = 8):
    """Per-chip decode-step time model for Llama-3-8B int8 at `tp`."""
    weight_bytes = 8.03e9           # int8 weights (+scales) of record
    flops = 2 * 8.03e9 * batch
    comm = cfg_layers * per_layer_ar_bytes   # bytes each chip moves/step
    t_compute = max(weight_bytes / tp / HBM_BW, flops / tp / PEAK_FLOPS)
    t_comm = comm / ICI_BW
    t = t_compute + t_comm
    t1 = max(weight_bytes / HBM_BW, flops / PEAK_FLOPS)
    eff = t1 / (tp * t)
    return t_compute, t_comm, t, eff


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--out", default=None,
                    help="also write the markdown table to this file")
    ap.add_argument("--measure-tp", type=int, default=0,
                    help="internal: measure one mesh size and print JSON")
    args = ap.parse_args()

    if args.measure_tp:
        import json
        print(json.dumps(measure(args.measure_tp, args.layers, args.batch,
                                 args.seq)))
        return 0

    rows = []
    for tp in (1, 2, 4, 8):
        b = measure_subprocess(tp, args.layers, args.batch, args.seq)
        rows.append((tp, b))
        print(f"tp={tp}: {b}", file=sys.stderr)

    # Megatron decode: 2 all-reduces/layer of the [B,1,D] activation.
    # Ring all-reduce per-chip traffic = 2*(tp-1)/tp * payload; HLO
    # reports the op's logical output bytes — convert per measured tp.
    per_layer = {}
    for tp, b in rows[1:]:
        ar = b["all-reduce"] / args.layers
        per_layer[tp] = ar * 2 * (tp - 1) / tp
    # extrapolate with the asymptote 2*payload (tp -> inf)
    payload = per_layer[8] / (2 * 7 / 8)

    lines = [
        "# TP scaling model (HLO-derived, fake-device sweep; a projection, "
        "not a measurement)",
        "",
        "Built by `tools/tp_scaling.py`: the REAL decode program "
        "(models/common.forward, Llama-3-8B layer geometry, "
        f"{args.layers} layers, batch {args.batch}) is compiled over "
        "fake-device `tensor` meshes and the collectives XLA/GSPMD "
        "inserted are read back out of the optimized HLO.",
        "",
        "## Measured collective volume per decode step",
        "",
        "| tp | all-reduce B (HLO, total) | per layer | per-chip ring bytes/layer |",
        "|---|---|---|---|",
    ]
    for tp, b in rows:
        ar = b["all-reduce"]
        pl = ar / args.layers
        ring = pl * 2 * (tp - 1) / tp if tp > 1 else 0
        lines.append(f"| {tp} | {ar:,} | {pl:,.0f} | {ring:,.0f} |")
    lines += [
        "",
        f"Per-layer all-reduce payload: {payload:,.0f} B "
        f"([B,1,D] activation x 2 sublayers) — INDEPENDENT of tp, as "
        "Megatron row/column sharding predicts: per-chip comm is flat "
        "while per-chip compute shrinks 1/tp.",
        "",
        "## Projected Llama-3-8B int8 decode scaling (v5e roofline)",
        "",
        f"HBM {HBM_BW/1e9:.0f} GB/s, ICI one-way {ICI_BW/1e9:.0f} GB/s, "
        "bf16 peak 197 TF/s; t = max(weights/tp/HBM, flops/tp/peak) + "
        "comm/ICI (no overlap assumed — pessimistic).",
        "",
        "| tp | compute ms | comm ms | step ms | scaling efficiency |",
        "|---|---|---|---|---|",
    ]
    for tp in (1, 2, 4, 8, 16, 32, 64):
        ring = payload * 2 * (tp - 1) / tp if tp > 1 else 0.0
        tc, tm, t, eff = model_row(tp, ring, batch=args.batch)
        lines.append(f"| {tp} | {tc*1e3:.3f} | {tm*1e3:.3f} | "
                     f"{t*1e3:.3f} | {eff*100:.1f}% |")
    lines += [
        "",
        "Reading: 8 -> 64 chips the per-chip comm term is flat "
        "(~2x payload over the ring) while compute shrinks linearly, so "
        "efficiency decays only through the fixed comm floor; XLA's "
        "latency-hiding scheduler overlaps much of it in practice, so "
        "these are LOWER bounds. Validation on real multi-chip hardware "
        "is the remaining step.",
        "",
    ]
    if args.out:
        Path(args.out).write_text("\n".join(lines))
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
