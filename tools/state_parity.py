"""What holds a model with recurrent layers (Mamba-2, Gated DeltaNet or
Mamba-1: the configuration's ONE recurrent kind) to its plain reference
over a
WHOLE stream, on the chip, at the published widths, through the server's
own path:

    python3 tools/state_parity.py <config.json> <out.json> [--toy]

A cell's own reference check (servebench/refcheck.py) feeds 16 tokens to
the contiguous forward: it never sees a slot reused, a chunk with filler,
the state on a block scan's donated carry, nor a state after thousands of
positions in the storage dtype. Here three requests go through the
Scheduler as `butterfly serve` runs it (mixed blocks of the packed step:
engine/serving.py _packed_scan; the lazy drain, inline finishes, the
window and its flush) over TWO slots: `long` (a prompt of LONG[0] tokens
as chunks of C, the last partly filler, then LONG[1] decode steps: the
rollout cell's stream), `first` beside it, and `second`, which waits and
is admitted into the slot `first` leaves, with `long`'s blocks in flight.
What is compared is the STATE each slot holds at the end (every recurrent
layer's heads' state, a head at a time, H [Nh, Hd, N] or S [H, dv, dk],
or a Mamba-1 layer's h [Di, N] a channel at a time, and the conv's last
K-1 inputs) with the state the
reference's position-by-position loop holds after the same tokens (the
prompt and all served tokens but the last, which is never fed back): rms
of the difference over the rms of the reference's, a layer; the WORST
layer's H of a slot is held to LIMITS' of the kind. States, not tokens: with seeded
random weights two programs part at the first near-tie, and a state is
what a leak changes first. Where the program keeps a state in bfloat16
the loop rounds H to bfloat16 after every position too (the reference's
`keep`), as the program's steps do: increments under half an ulp of a
slow head's state are lost, the same in both. Beside that reading the
float32 loop's is reported as `drift`, what the storage dtype costs over
the stream, and held to nothing.

The seeded weights forget within ten positions (A = -1, dt = 0.69), which
would hide every fault here, so the tool gives `A_log` and `dt_bias` the
family's own initial ranges (A 1-16, dt 0.001-0.1, seeded; a Gated
DeltaNet head's decay has the same form, exp(-A softplus(a + dt_bias))):
heads that remember from one position to a thousand, in the program and
the reference alike.

A configuration whose `max_seq` does not hold LONG's stream (the
hybrid of 400 positions) is given LONG's prompt and as many new tokens as
fit, less a page: its long stream is then under 400 positions, over 256.

Beside the clean run, two faults planted in the program (`long` cut to
LONG_SHORT new tokens: it only has to outlast the other two), each of
which must pass the limit in the reused slot:
`no_reset` (a chunk at position 0 does not start from zero: the slot's
last tenant leaks) and `filler_advances` (a chunk's filler columns go
through the recurrence). A reading means something only between the clean
run's and a fault's, and the kind's limit lies there (PERF.md, PRs 41
and 56, gives the readings each was set from).

The tool reports chip evidence and refuses to run without a TPU; `--toy`
(the CPU rehearsal of tests/test_granite_hybrid.py) says so in its output.
"""
import contextlib
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

#: rms of the difference over the rms of the reference's state: a slot
#: whose worst layer is above it holds another stream's state. Set between
#: the chip's readings (PERF.md, PR 41): the clean run 0.0218 (the long
#: stream's slot after 2,247 positions; the reused slot 0.0154) and
#: `no_reset` 0.0728 in the reused slot (1.8 times of room on either
#: side; `filler_advances` reads 0.328). A Gated DeltaNet model's is its
#: own (PERF.md, PR 56, second session): the clean run reads 0.0060 in
#: the first layer and climbs with depth to 0.0360 in the 24th (long,
#: 383 positions; the reused slot 0.0353: a state's input is the stream,
#: 0.02 off the reference's by then, refcheck), `no_reset` 0.2357 and
#: `filler_advances` 0.3669: their geometric middle, 2.5 times of room.
#: A Mamba-1 model's (PERF.md, PR 58, on the mixers as they are seeded
#: live: models/common.py MAMBA1_SEEDS): the clean run 0.0057 in the
#: first layer to 0.0930 in the 26th (long, 2,247 positions; the reused
#: slot 0.0873 after 139: depth, not length; the stream a state is fed
#: is 5 % off the reference's by then, as the logits are), `no_reset`
#: 0.3108 and `filler_advances` 1.1029: the geometric middle of 0.0930
#: and 0.3108, 1.8 times of room
LIMITS = {"mamba": 0.04, "linear_attention": 0.09, "mamba1": 0.17}
SLOTS = 2
#: (prompt, new tokens) of each request; a chunk is 32 wide
LONG, FIRST, SECOND = (200, 2048), (70, 40), (100, 40)
LONG_SHORT = 256
FAULTS = ("clean", "no_reset", "filler_advances")


@contextlib.contextmanager
def planted(fault: str):
    """The Mamba layer of the packed step replaced while the fault's
    programs are traced and run."""
    import jax.numpy as jnp
    from butterfly_tpu.cache import paged
    real = paged.advance_packed

    def faulty(x, lp, mp, state, m, rows, cfg, use_kernel=False):
        if fault == "no_reset":         # no chunk is at position 0
            rows = rows._replace(chunk_pos=rows.chunk_pos + 1)
        elif fault == "filler_advances":  # every column of a chunk is real
            S, C = rows.active.shape[0], rows.chunk_pos.shape[1]
            rows = rows._replace(ok=rows.ok.at[S:].set(
                jnp.repeat(rows.chunk_ok, C)))
        return real(x, lp, mp, state, m, rows, cfg, use_kernel)

    paged.advance_packed = real if fault == "clean" else faulty
    try:
        yield
    finally:
        paged.advance_packed = real


def remembering(params, seed: int, stack: str = "mamba"):
    """The tree with `A_log` and `dt_bias` of the recurrent kind's
    stack (params["mamba"], "gdn" or "mamba1") drawn from Mamba-2's
    initial ranges (A uniform 1-16; dt log-uniform 0.001-0.1, its bias
    the inverse softplus), each in its leaf's own shape (a number a
    head, or Mamba-1's a channel and a state index and a channel)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    mixers = dict(params[stack])
    shape, dtype = mixers["A_log"].shape, mixers["A_log"].dtype
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                            mixers["dt_bias"].shape))
    mixers["A_log"] = jnp.asarray(np.log(rng.uniform(1, 16, shape)), dtype)
    mixers["dt_bias"] = jnp.asarray(np.log(np.expm1(dt)), dtype)
    return {**params, stack: mixers}


def served_states(cfg, params, rt, prompts, new, fault="clean"):
    """The requests through the scheduler: ([tokens each request
    served], [the slot it held], the engine's SSMState on the host)."""
    import jax
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.sched.scheduler import Scheduler
    with planted(fault):
        eng = ServingEngine(Model(cfg), params, rt)
        sched = Scheduler(eng, seed=0)
        reqs = [sched.submit(list(map(int, p)), max_new_tokens=n)
                for p, n in zip(prompts, new)]
        slots = [None] * len(reqs)
        while sched.has_work:
            sched.tick()
            slots = [s if r.slot is None else r.slot
                     for s, r in zip(slots, reqs)]
        state = jax.device_get(eng._ssm_state)
    return [list(r.output) for r in reqs], slots, state


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def slot_reading(state, slot, held, heads=lambda h: h):
    """{h, conv: [a layer's reading]} of one slot against the
    reference's states (`held`: [(H, tail)] in layer order). heads: a
    slot's state of one layer as the program holds it, to the
    reference's a head at a time."""
    return {"h": [_rel(heads(state.h[m, slot]), H)
                  for m, (H, _) in enumerate(held)],
            "conv": [_rel(state.conv[m, :, slot], t)
                     for m, (_, t) in enumerate(held)]}


def check(config: dict, toy: bool = False, seed: int = 41,
          requests=(LONG, FIRST, SECOND), long_short=LONG_SHORT) -> dict:
    import jax
    import jax.numpy as jnp
    from butterfly_tpu.cache.ssm_state import gdn_heads_of
    from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
    from butterfly_tpu.models.common import RECURRENT_NAMES, RECURRENT_STACKS
    from butterfly_tpu.quant.int8 import init_params_by_leaf, is_quantized_leaf
    from servebench.launcher import model_fields
    from servebench.refcheck import leaf_reader, load_reference

    kind = str(jax.devices()[0].device_kind)
    if jax.default_backend() != "tpu" and not toy:
        raise SystemExit(f"no TPU here ({kind}): this is chip evidence; "
                         "--toy rehearses on the CPU and says so")
    cfg = ModelConfig(**model_fields(config))
    sv = config["serve"]
    rt = RuntimeConfig(
        max_batch_size=SLOTS, max_seq_len=sv["max_seq"],
        page_size=sv["page_size"], kv_quant=sv.get("kv_quant", "none"),
        decode_steps_per_tick=sv["decode_steps_per_tick"],
        prefill_inline_budget=sv.get("prefill_inline_budget", 32))
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    if requests[0] == LONG and sum(LONG) > rt.max_seq_len:
        requests = ((LONG[0], rt.max_seq_len - LONG[0] - rt.page_size),) \
            + tuple(requests[1:])
        long_short = min(long_short, requests[0][1])
    if not cfg.has_ssm or any(p + n > rt.max_seq_len or p % C == 0
                              for p, n in requests):
        raise ValueError(
            "a model with recurrent layers, and requests that fit max_seq "
            f"{rt.max_seq_len} with a last chunk of {C} partly filler")
    params = remembering(
        init_params_by_leaf(cfg, jax.random.PRNGKey(0),
                            quant=sv.get("quant", "none")), seed,
        RECURRENT_STACKS[cfg.recurrent_kind])
    # a slot's state of one layer as the reference holds it: Mamba-2's as
    # held; Gated DeltaNet's lanes-of-a-group layout a head at a time;
    # Mamba-1's [N, Di] a channel at a time
    heads = {"linear_attention": lambda h: np.asarray(gdn_heads_of(
        jnp.asarray(h, jnp.float32)[None], cfg)[0]),
        "mamba1": lambda h: np.asarray(h).T}.get(cfg.recurrent_kind,
                                                 lambda h: h)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, p).astype(np.int32)
               for p, _ in requests]
    reference = load_reference(config["reference"])
    leaf = leaf_reader(params, is_quantized_leaf)
    store = jnp.dtype(cfg.dtype)    # what a slot keeps between steps
    limit = LIMITS[cfg.recurrent_kind]
    out = {"device": kind, "evidence": "cpu toy" if toy else "chip",
           "kind": RECURRENT_NAMES[cfg.recurrent_kind],
           "limit": limit, "chunk_width": C, "slots": SLOTS,
           "requests": {name: {"prompt": p, "new": n} for name, (p, n)
                        in zip(("long", "first", "second"), requests)}}
    for fault in FAULTS:
        clean = fault == "clean"
        new = [requests[0][1] if clean else long_short, requests[1][1],
               requests[2][1]]
        served, slots, state = served_states(cfg, params, rt, prompts, new,
                                             fault)
        gc.collect()
        got = out[fault] = {"slots": slots}
        if slots[0] == slots[1] or slots[2] != slots[1]:
            raise RuntimeError(f"{fault}: `second` was to take the slot "
                               f"`first` left: {slots}")
        # a fault is read in the reused slot alone
        for name, i in (("long", 0), ("second", 2))[0 if clean else 1:]:
            seq = np.concatenate([prompts[i], served[i][:-1]]).astype(np.int32)
            got[name] = {"positions": int(len(seq))}
            # beside the float32 loop (`drift`: what keeping a state in
            # the storage dtype costs), and beside the loop that rounds
            # H to that dtype after every position, as the program's
            # steps do (the path: what the limit holds)
            for keep in (None, store)[:2 if store != jnp.float32 else 1]:
                held = []
                reference.logits(seq, leaf, config, rows=[0], states=held,
                                 keep=keep)
                read = slot_reading(state, slots[i], held, heads)
                if keep is None and store != jnp.float32:
                    got[name].update(drift=read["h"],
                                     drift_worst=max(read["h"]))
                else:
                    got[name].update(h_worst=max(read["h"]),
                                     conv_worst=max(read["conv"]), **read)
    clean = out["clean"]
    out["ok"] = bool(
        all(clean[n]["h_worst"] < limit for n in ("long", "second"))
        and all(out[f]["second"]["h_worst"] > limit for f in FAULTS[1:]))
    return out


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--toy"]
    result = check(json.loads(Path(args[0]).read_text()),
                   toy="--toy" in sys.argv)
    Path(args[1]).parent.mkdir(parents=True, exist_ok=True)
    Path(args[1]).write_text(json.dumps(result))
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
