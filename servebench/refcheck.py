#!/usr/bin/env python3
"""Compare the program's model with the configuration's plain reference,
on the chip, outside the measured window.

    python3 servebench/refcheck.py --config servebench/configs/<name>.json --seed N --out result.json

Run by the harness as a child BEFORE the server starts (one process
holds the chip at a time). It builds the weights exactly as `butterfly
serve` does (`serve/cli.py:load_params`: PRNGKey(0), the configuration's
quantization and mesh), runs the program's model over a seeded sample of
short prompts (prefill through the cache, then decode calls through it)
and compares LOGITS, not tokens, with the configuration's float32
reference.

The width of a decode call is the configuration file's top-level
`decode_width` (1 where the file has none: a model that yields a token
a step). At width 1 the prefill is PREFILL tokens and DECODE calls of
one token follow. A model that generates by blocks of w positions sees
the whole block from each of its positions, so the program is fed as its
timed path feeds it: a prefill of whole blocks (the largest multiple of
w not over PREFILL, one block at least), then calls of w positions
through the cache, two of them or DECODE tokens' worth if that is more,
so that one decoded block attends a block that an earlier decode call
wrote. EVERY row of
each call is compared with the reference's row at that position, the
prefill's last row as well. The check's cache holds CACHE positions; a
width whose prefill and calls do not fit it is an error that names the
key.

The reference owns the names of its weights. A file
`<path>/references/<name>.py` under one of the manifest's `paths`
(the configuration's "reference" is the name) gives

    logits(tokens, leaf, config) -> float32 [T, V]

`leaf(path, layer=None)` hands over the leaf at a `/`-joined path of the
program's parameter tree ("layers/attn/wq", "layers/moe/router") as
float32: a quantized leaf as codes times scales, and one layer of a
stacked leaf when `layer` is given (an index, or the leading indices as a
tuple: layer and expert), so that a reference holds one layer's, or one
expert's, float32 weights at a time. `config` is the configuration FILE:
the reference takes its sizes from the published keys, not from the
program's ModelConfig. This file knows no leaf name of any family.

What a reference for a wide step owes: `logits` is still ONE full
forward of the whole sequence, and its row p is what position p reads
under the configuration's OWN mask (for generation by blocks of B:
position p attends j where j // B <= p // B). The reference owns that
mask as it owns its leaf names; this file knows only how many positions
a call carries. A program whose calls of w positions are causal inside
the call differs from such a reference at every row compared, the last
row of a block too once there are two layers (tests/servebench/: by
hundreds of times a float32 limit; PERF.md has the reading against a
bfloat16 one), and so does a block-masked program from a causal
reference.

The error is the root of the mean squared difference over the standard
deviation of the reference's logits at that position, the worst over
the positions compared (the largest single difference is reported
beside it). The limit is the configuration's `reference_tolerance`, with
`reference_tolerance_why` beside it in the file and in the result; where
a file gives none it is TOLERANCE, which the chip set for a dense model
in bfloat16 through 32 layers (see PERF.md): a path that dropped to a
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
from servebench.manifest import decode_width, find_under_paths, load_manifest  # noqa: E402

#: rms logit difference / std of the reference's logits, where the
#: configuration's file states no `reference_tolerance`
TOLERANCE = 0.12
PROMPTS, PREFILL, DECODE = 2, 12, 4
#: positions the check's cache holds
CACHE = 32


def load_reference(name: str, paths=None):
    """`references/<name>.py` under the first of the manifest's paths
    (or of `paths`) that holds it, as readers and traffic files are."""
    path = find_under_paths({"paths": paths} if paths else load_manifest(ROOT),
                            ROOT, "references", name + ".py")
    spec = importlib.util.spec_from_file_location("servebench_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def leaf_reader(params, is_quantized):
    """`leaf(path, layer=None)` over a parameter tree (see above)."""
    import jax.numpy as jnp

    def leaf(path: str, layer=None):
        node = params
        for key in path.split("/"):
            if not isinstance(node, dict) or key not in node \
                    or is_quantized(node):
                raise KeyError(f"the program's parameters have no leaf "
                               f"{path!r} (at {key!r})")
            node = node[key]
        parts = [node["q8"], node["s"]] if is_quantized(node) else [node]
        if isinstance(parts[0], dict):
            raise KeyError(f"{path!r} is a group of leaves: "
                           + ", ".join(sorted(parts[0])))
        if layer is not None:
            parts = [x[layer] for x in parts]
        out = parts[0].astype(jnp.float32)
        return out * parts[1].astype(jnp.float32) if len(parts) == 2 else out
    return leaf


def lengths(width: int) -> tuple:
    """(prefill, decode) tokens of one sequence at a decode width: whole
    blocks before the first decode call, one at least, then two calls or
    more."""
    prefill = max(1, PREFILL // width) * width
    decode = max(DECODE, 2 * width)
    if prefill + decode > CACHE:
        raise ValueError(
            f"`decode_width` {width}: a prefill of {prefill} and "
            f"{decode // width} calls of {width} do not fit the check's "
            f"cache of {CACHE} positions")
    return prefill, decode


def build(config: dict, paths=None) -> SimpleNamespace:
    """The program's model with the weights `butterfly serve` would hold
    for this configuration, and the configuration's reference over them."""
    import jax
    from butterfly_tpu.core.config import ModelConfig
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.quant.int8 import is_quantized_leaf
    from butterfly_tpu.serve import cli
    width = decode_width(config)
    lengths(width)          # a width that does not fit fails before the weights
    cfg = ModelConfig(**model_fields(config))
    model = Model(cfg)
    serve = config["serve"]
    ns = SimpleNamespace(ckpt=None, quant=serve.get("quant", "none"),
                         tensor_parallel=serve.get("tensor_parallel", 1))
    params = cli.load_params(model, ns, cli.build_mesh(ns))
    return SimpleNamespace(
        cfg=cfg, model=model, params=params, width=width,
        fwd=jax.jit(lambda p, t, c: model(p, t, c)),
        ref=load_reference(config["reference"], paths=paths),
        leaf=leaf_reader(params, is_quantized_leaf))


def sample(seed: int, vocab: int, width: int = 1) -> list:
    """The seed's PROMPTS sequences of token ids, prefill and decode
    (`lengths`: PREFILL + DECODE at width 1)."""
    rng = random.Random(int(seed))
    return [[rng.randrange(1, vocab) for _ in range(sum(lengths(width)))]
            for _ in range(PROMPTS)]


def program_rows(b: SimpleNamespace, toks: list) -> list:
    """[(position, logits [V])] of the program: the prefill's last
    position, then every row of each decode call of `b.width` positions
    through the cache."""
    import jax.numpy as jnp
    prefill = lengths(b.width)[0]
    cache = b.model.init_cache(1, CACHE)
    got, cache = b.fwd(b.params, jnp.asarray([toks[:prefill]], jnp.int32), cache)
    rows = [(prefill - 1, got[0, -1])]
    for j in range(prefill, len(toks), b.width):
        got, cache = b.fwd(b.params,
                           jnp.asarray([toks[j:j + b.width]], jnp.int32), cache)
        rows += [(j + i, got[0, i]) for i in range(b.width)]
    return rows


def errors(rows: list, want) -> tuple:
    """(rms, largest) difference of `rows` from the reference's logits
    `want` [T, V], each over the standard deviation of the reference's
    logits at the position; the worst over the positions."""
    import jax.numpy as jnp
    worst = big = 0.0
    for j, g in rows:
        d = g.astype(jnp.float32) - want[j]
        sd = jnp.std(want[j])
        worst = max(worst, float(jnp.sqrt(jnp.mean(d * d)) / sd))
        big = max(big, float(jnp.max(jnp.abs(d)) / sd))
    return worst, big


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
    devs = jax.devices()
    if args.require_tpu and (devs[0].platform != "tpu"
                             or len(devs) < args.require_tpu):
        return NO_CHIP
    from butterfly_tpu.core.compile_cache import place_compile_cache
    place_compile_cache()
    b = build(config)
    tolerance = float(config.get("reference_tolerance", TOLERANCE))
    worst = big = 0.0
    positions = 0
    for toks in sample(args.seed, b.cfg.vocab_size, b.width):
        rows = program_rows(b, toks)
        rms, most = errors(rows, b.ref.logits(toks, b.leaf, config))
        worst, big = max(worst, rms), max(big, most)
        positions += len(rows)
    out = {"ok": worst <= tolerance, "rms_err": worst, "max_err": big,
           "tolerance": tolerance,
           "tolerance_why": config.get(
               "reference_tolerance_why",
               "servebench/refcheck.py TOLERANCE: no limit in the file"),
           "reference": config["reference"],
           "positions": positions, "decode_width": b.width,
           "seconds": time.monotonic() - t0,
           "platform": devs[0].platform}
    Path(args.out).write_text(json.dumps(out))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
