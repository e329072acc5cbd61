"""Whether the benchmark's reference check SEES a Mamba-1 mixer, on the
chip, at the published widths:

    python3 tools/mixer_faults.py servebench/configs/jamba2-3b.json out.json [--seeds 3]

`servebench/refcheck.py` decides `correct` from the program's logits
against the configuration's plain reference. This tool reads the same
number with the same functions (`build`, `sample`, `program_rows`,
`errors`: the worst of a seed's rows), first for the program as it is
(`clean`) and then for the program over weights in which ONE thing that
makes the mixer Mamba-1 is taken away, the reference keeping the sound
weights:

  one_rate_a_channel  A[n, c] is its mean over the state index n: a
                      channel's 16 state values decay alike, as a head's
                      do under Mamba-2's scalar
  one_step_a_layer    dt is one number a layer: dt_proj zero and dt_bias
                      its mean (no step size of a channel's own)
  no_dt_norm, no_b_norm, no_c_norm
                      one of the family's three inner norms dropped
  tail_lost           the conv reads its own position alone (the taps of
                      the three positions before it are zero): a slot's
                      conv tail is never read
  state_lost          every rate is 1e4 times itself: h forgets within a
                      position, and the readout sees the position's own
                      dt B u alone

Exit code 0 when the clean reading of every seed is at or under the
configuration's `reference_tolerance` and every fault's is over it on
every seed: a fault that stays under the limit is one `correct` cannot
see, and the output names it. It refuses to run without a TPU (`--toy`
rehearses on the CPU, as tests/test_jamba.py does at a toy's size).
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

FAULTS = ("one_rate_a_channel", "one_step_a_layer", "no_dt_norm",
          "no_b_norm", "no_c_norm", "tail_lost", "state_lost")


def faulted(params, fault: str):
    """The tree with `fault` planted in its Mamba-1 stack."""
    import jax.numpy as jnp
    mp = dict(params["mamba1"])
    f32 = jnp.float32
    if fault == "one_rate_a_channel":
        A = jnp.exp(mp["A_log"].astype(f32))                 # [Lm, N, Di]
        mp["A_log"] = jnp.log(jnp.broadcast_to(
            A.mean(1, keepdims=True), A.shape)).astype(mp["A_log"].dtype)
    elif fault == "one_step_a_layer":
        b = mp["dt_bias"].astype(f32)
        mp["dt_bias"] = jnp.broadcast_to(
            b.mean(1, keepdims=True), b.shape).astype(mp["dt_bias"].dtype)
        mp["dt_proj"] = jnp.zeros_like(mp["dt_proj"])
    elif fault in ("no_dt_norm", "no_b_norm", "no_c_norm"):
        del mp[fault[3:]]
    elif fault == "tail_lost":
        mp["conv_w"] = mp["conv_w"].at[:, :-1].set(0)        # [Lm, K, Di]
    elif fault == "state_lost":
        mp["A_log"] = (mp["A_log"].astype(f32)
                       + jnp.log(1e4)).astype(mp["A_log"].dtype)
    else:
        raise ValueError(f"no fault {fault!r}")
    return {**params, "mamba1": mp}


def check(config: dict, seeds, paths=None) -> dict:
    from types import SimpleNamespace

    from servebench import refcheck
    b = refcheck.build(config, paths=paths)
    if b.cfg.recurrent_kind != "mamba1":
        raise ValueError(f"{config['name']} has no Mamba-1 layer")
    limit = float(config.get("reference_tolerance", refcheck.TOLERANCE))
    readings = {name: [] for name in ("clean",) + FAULTS}
    for seed in seeds:
        samples = refcheck.sample(seed, b.cfg.vocab_size, b.width)
        wants = [b.ref.logits(toks, b.leaf, config) for toks in samples]
        for name in readings:
            run = b if name == "clean" else SimpleNamespace(
                **{**vars(b), "params": faulted(b.params, name)})
            readings[name].append(max(
                refcheck.errors(refcheck.program_rows(run, toks), want)[0]
                for toks, want in zip(samples, wants)))
        print(json.dumps({"seed": seed, **{k: v[-1] for k, v in
                                           readings.items()}}), flush=True)
    unseen = [f for f in FAULTS if min(readings[f]) <= limit]
    return {"config": config["name"], "limit": limit, "seeds": list(seeds),
            "readings": readings, "unseen": unseen,
            "ok": max(readings["clean"]) <= limit and not unseen}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("out")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 5800)
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()
    t0 = time.monotonic()
    config = json.loads(Path(args.config).read_text())
    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu" and not args.toy:
        print("mixer_faults: no TPU (--toy rehearses on the CPU)",
              file=sys.stderr)
        return 2
    from butterfly_tpu.core.compile_cache import place_compile_cache
    place_compile_cache()
    out = check(config, [args.first_seed + 13 * i for i in range(args.seeds)])
    out.update(platform=dev[0].platform, toy=args.toy,
               seconds=time.monotonic() - t0)
    Path(args.out).write_text(json.dumps(out))
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
