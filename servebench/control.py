#!/usr/bin/env python3
"""The two readings a limit of the reference check is set from, on the chip
at the cell's own size, in one process:

    python3 servebench/control.py --config servebench/configs/<name>.json --seeds 12 --control-seeds 3 --out result.json

`sound`: the program against its plain reference (what
servebench/refcheck.py reads in a traced run), on `--seeds` seeds.
`control`: the reference put in the program's place, computed in the
nearest precision BELOW the one the configuration states, against the
reference itself, on `--control-seeds` of the same seeds. The ladder:
float32 -> bfloat16; bfloat16 -> int8 (fp8 is read beside it); int8 ->
int4. The lower precision is the weights' alone (one scale per output
channel, symmetric, as the program's own int8 path has it): a reference
owns its arithmetic, and what a later PR is tempted by is a cheaper
weight format. The control has to come out NOT correct: a limit that
holds lies above the largest sound reading and below the smallest
control reading, with the control at three times the sound or more. The
benchmark's own runs never call this file; tests/servebench/ keeps it as
a test at a toy's size, and PERF.md gives the chip's readings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from servebench import refcheck  # noqa: E402

#: what the configuration states -> the precisions read as controls, the
#: first of which is the control
LADDER = {"float32": ("bfloat16",), "bfloat16": ("int8", "fp8"),
          "int8": ("int4",)}


def stated(config: dict) -> str:
    """The precision the configuration states for its weights."""
    if config["serve"].get("quant", "none") == "int8":
        return "int8"
    return config.get("torch_dtype", "bfloat16")


def lowered(x, kind: str):
    """A float32 leaf rounded to `kind` and back; a vector (a norm's
    scale) is left as it is."""
    import jax.numpy as jnp
    if x.ndim < 2:
        return x
    if kind == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    top = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    if kind == "fp8":
        scale = jnp.where(top > 0, top / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    qmax = {"int8": 127.0, "int4": 7.0}[kind]
    scale = jnp.where(top > 0, top / qmax, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def measure(config: dict, seeds, control_seeds, paths=None) -> dict:
    b = refcheck.build(config, paths=paths)
    kinds = LADDER[stated(config)]
    sound, control = [], {k: [] for k in kinds}
    for n, seed in enumerate(seeds):
        rms = {k: 0.0 for k in ("sound",) + kinds}
        for toks in refcheck.sample(seed, b.cfg.vocab_size, b.width):
            want = b.ref.logits(toks, b.leaf, config)
            rows = refcheck.program_rows(b, toks)
            rms["sound"] = max(rms["sound"], refcheck.errors(rows, want)[0])
            if n < control_seeds:
                for k in kinds:
                    low = b.ref.logits(
                        toks, lambda path, layer=None, k=k:
                        lowered(b.leaf(path, layer), k), config)
                    rms[k] = max(rms[k], refcheck.errors(
                        [(j, low[j]) for j, _ in rows], want)[0])
        sound.append(rms["sound"])
        if n < control_seeds:
            for k in kinds:
                control[k].append(rms[k])
        print(json.dumps({"seed": seed, **rms}), flush=True)
    limit = float(config.get("reference_tolerance", refcheck.TOLERANCE))
    least = min(control[kinds[0]]) if control[kinds[0]] else None
    return {"config": config["name"], "stated": stated(config),
            "control_is": kinds[0], "limit": limit, "seeds": list(seeds),
            "sound": sound, "control": control,
            "sound_max": max(sound), "control_min": least,
            "ratio": least / max(sound) if least else None,
            "holds": bool(least) and max(sound) <= limit < least
            and least >= 3 * max(sound)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 2600)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    t0 = time.monotonic()
    config = json.loads(Path(args.config).read_text())
    import jax
    from butterfly_tpu.core.compile_cache import place_compile_cache
    place_compile_cache()
    out = measure(config, [args.first_seed + 17 * i for i in range(args.seeds)],
                  args.control_seeds)
    dev = jax.devices()
    out.update(platform=dev[0].platform, devices=len(dev),
               seconds=time.monotonic() - t0)
    Path(args.out).write_text(json.dumps(out))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
