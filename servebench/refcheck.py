#!/usr/bin/env python3
"""Compare the program's model with the configuration's plain reference,
on the chip, outside the measured window.

    python3 servebench/refcheck.py --config servebench/configs/<name>.json --seed N --out result.json

Run by the harness as a child BEFORE the server starts (one process
holds the chip at a time). It builds the weights exactly as `butterfly
serve` does (`serve/cli.py:load_params`: PRNGKey(0), the configuration's
quantization and mesh), runs the program's model over a seeded sample of
short prompts (prefill through the cache, then decode steps through it)
and compares LOGITS, not tokens, with the float32 reference
(servebench/references/), which is given the same weights one layer at a
time (a quantized leaf as codes times scales, in float32).

The error is the root of the mean squared difference over the standard
deviation of the reference's logits at that position, the worst over
the positions compared (the largest single difference is reported
beside it). TOLERANCE is set from what the chip
measured (see PERF.md): the program computes in bfloat16, whose rounding
through 32 layers accounts for the error seen; a path that dropped to a
lower precision than the configuration states, or left out a term, lands
well outside it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import random
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from servebench.launcher import NO_CHIP, model_fields  # noqa: E402

#: rms logit difference / std of the reference's logits
TOLERANCE = 0.12
PROMPTS, PREFILL, DECODE = 2, 12, 4


def load_reference(name: str):
    path = ROOT / "servebench" / "references" / (name + ".py")
    spec = importlib.util.spec_from_file_location("servebench_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--require-tpu", type=int, default=0)
    args = ap.parse_args()
    t0 = time.monotonic()
    config = json.loads(Path(args.config).read_text())
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    if args.require_tpu and (devs[0].platform != "tpu"
                             or len(devs) < args.require_tpu):
        return NO_CHIP
    from butterfly_tpu.core.compile_cache import place_compile_cache
    from butterfly_tpu.core.config import ModelConfig
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.quant.int8 import is_quantized_leaf
    from butterfly_tpu.serve import cli
    place_compile_cache()
    cfg = ModelConfig(**model_fields(config))
    model = Model(cfg)
    serve = config["serve"]
    ns = SimpleNamespace(ckpt=None, quant=serve.get("quant", "none"),
                         tensor_parallel=serve.get("tensor_parallel", 1))
    mesh = cli.build_mesh(ns)
    params = cli.load_params(model, ns, mesh)
    ref = load_reference(config["reference"])

    def f32(leaf, i=None):
        """A leaf (of layer i) as float32; codes times scales if int8."""
        if is_quantized_leaf(leaf):
            q, s = (leaf["q8"], leaf["s"]) if i is None else \
                (leaf["q8"][i], leaf["s"][i])
            return q.astype(jnp.float32) * s.astype(jnp.float32)
        return (leaf if i is None else leaf[i]).astype(jnp.float32)

    lp = params["layers"]

    def layer_weights(i):
        return {"ln1": f32(lp["ln1"]["scale"], i), "ln2": f32(lp["ln2"]["scale"], i),
                **{k: f32(lp["attn"][k], i) for k in ("wq", "wk", "wv", "wo")},
                **{k: f32(lp["mlp"][k], i) for k in ("w_gate", "w_up", "w_down")}}

    fwd = jax.jit(lambda p, t, c: model(p, t, c))
    rng = random.Random(int(args.seed))
    worst = big = 0.0
    T = PREFILL + DECODE
    for _ in range(PROMPTS):
        toks = [rng.randrange(1, cfg.vocab_size) for _ in range(T)]
        want = ref.logits(toks, f32(params["embed"]["tok"]), layer_weights,
                          cfg.num_layers, f32(params["final_norm"]["scale"]),
                          f32(params["lm_head"]), cfg.norm_eps, cfg.rope_theta)
        cache = model.init_cache(1, 32)
        got, cache = fwd(params, jnp.asarray([toks[:PREFILL]], jnp.int32), cache)
        rows = [(PREFILL - 1, got[0, -1])]
        for j in range(PREFILL, T):
            got, cache = fwd(params, jnp.asarray([[toks[j]]], jnp.int32), cache)
            rows.append((j, got[0, -1]))
        for j, g in rows:
            d = g.astype(jnp.float32) - want[j]
            err = float(jnp.sqrt(jnp.mean(d * d)) / jnp.std(want[j]))
            big = max(big, float(jnp.max(jnp.abs(d)) / jnp.std(want[j])))
            worst = max(worst, err)
    out = {"ok": worst <= TOLERANCE, "rms_err": worst, "max_err": big,
           "tolerance": TOLERANCE,
           "positions": PROMPTS * (DECODE + 1), "seconds": time.monotonic() - t0,
           "platform": devs[0].platform}
    Path(args.out).write_text(json.dumps(out))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
