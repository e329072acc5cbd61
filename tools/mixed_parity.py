"""What holds the packed mixed step to the lane-wide step it replaced, on the
chip, at a cell's `serve` settings:

    python3 tools/mixed_parity.py <config.json> <out.json> [--toy]

Logits, not tokens: with the seeded random weights every cell runs, logits
are nearly flat and any two programs part within a few tokens (PERF.md,
PR 29), so the reading is the rms of the difference over the reference's
spread, per row, as servebench/refcheck.py reads it.

Two states run side by side from one fork, for three blocks of k steps with
a flush after each, as the scheduler drains: `paged_forward_packed`
(S decode rows + P chunks) and the lane-wide `paged_forward_window` at
`[S, C]` (every lane a C-wide chunk, the chain token broadcast), each
writing and reading its OWN window and pool, the next tokens those of the
lane-wide side. S - P slots decode from prompts of `batch`'s lengths; P
slots are admitted at the fork and prefill in chunks of C, then decode. So
a chunk's keys are read back by its slot's later chunks from the window,
and after the flush from the pool, by the chunk and then by the decode row
the slot becomes. Once with P = 1 (the cells' setting) and once with P = 2.

Beside the clean run, two planted faults on the packed side, from the same
fork. `chunk_shift` feeds every chunk its prompt one token late: a reading
means something only between the clean run's and this one's, LIMIT lies
there (PERF.md, PR 29 gives the readings it was set from; LIMITS has a
recurrent kind's own), and the run is
`ok` when the clean run stays under it and the fault passes it, for both P.
`index_shift` starts a chunk slot's staged count one too high, so its
entries land one window index and one position late behind an entry nobody
wrote: rotary attention is relative, so this is the mildest positional
fault there is, and `index_shift_seen` says whether this reading resolves
it from the noise between two programs (in float32 on the CPU it does).

A model with RECURRENT layers (Mamba-2, Gated DeltaNet) has no lane-wide
step to stand beside: `paged_forward_window` refuses it, its state being
advanced by the packed step alone. Its packed step is read against the
configuration's PLAIN REFERENCE instead (`run_recurrent`): every slot is
fed a fixed token stream of its own (teacher-forced, so both sides see the
same tokens whatever a near-tie decides), the slots prefill through the
packed step's own chunks, P slots are admitted mid-block with prompts of
2 C + 6 and C + C/2 + 3 tokens (the first crosses TWO chunk edges and a
flush, its last chunk mostly filler; refcheck.py's 16-token prompts stay
inside one chunk) and then decode, and the logits of a few slots' rows (the
chunk slots and two decoding ones) are held to the reference's rows at the
same positions. `chunk_shift` is planted as above; `index_shift` has no
meaning where nothing rotates and is left out.

The tool reports chip evidence and refuses to run without a TPU; `--toy`
(the CPU rehearsal of tests/test_mixed_dispatch.py) says so in its output.
"""
import json
import sys
from functools import partial
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

#: rms of the difference over the reference's spread: above it a row is wrong
LIMIT = 0.2
#: a recurrent kind read against its plain reference has its own where
#: the chip has read it (PERF.md, PR 56, second session: Olmo-Hybrid-7B
#: clean 0.0199-0.0202 at every row, P 1 and 2; a chunk fed a token late
#: 0.32 at the median decode row and 1.38 at a chunk's: their geometric
#: middle. The first tree's order, decode rows before the chunks, read
#: 0.130-0.138 at the decode rows of P 2: over this, under LIMIT)
#: A Mamba-1 model keeps LIMIT (PERF.md, PR 58: Jamba2-3B clean 0.051
#: at the median row and 0.059, 0.061 at the worst of 66 and 85, a
#: chunk fed a token late 1.43)
LIMITS = {"linear_attention": 0.08}
BLOCKS = 3
FAULTS = ("clean", "chunk_shift", "index_shift")


def _reading(a, b):
    """Per row: rms(a - b) over the spread of b."""
    return np.sqrt(((a - b) ** 2).mean(-1)) / b.std(-1)


def run(cfg, params, mesh, sv, P, seed=29):
    """The side-by-side run for P chunks; returns readings per fault."""
    import jax
    import jax.numpy as jnp
    from butterfly_tpu.cache.paged import (
        flush_paged_window, paged_forward_packed, paged_forward_window)
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model

    k, S = sv["decode_steps_per_tick"], sv["max_batch"]
    # the cell's settings, but a pool of 256 tokens a slot: four sides
    # each flush into a pool of their own, and no slot here grows longer
    per = min(sv["max_seq"], 256) // sv["page_size"]
    rt = RuntimeConfig(max_batch_size=S, max_seq_len=sv["max_seq"],
                       page_size=sv["page_size"], num_pages=S * per,
                       kv_quant=sv.get("kv_quant", "none"),
                       decode_steps_per_tick=k)
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    # the engine as the factory of the cell's state: pool, window and
    # weights in their layout, the mesh, the kernels' switch
    eng = ServingEngine(Model(cfg), params, rt, mesh=mesh)
    for s in range(S):
        eng.set_table_row(s, list(range(s * per, (s + 1) * per)))
    eng._ensure_window(k * C)
    eng._sync_table()
    rng = np.random.default_rng(seed)
    # batch's prompts are 32-128 tokens; cut to what a toy's window holds
    hi = min(128, k * C, sv["max_seq"] // 3)
    plens = np.exp(rng.uniform(np.log(hi / 4), np.log(hi), S)).astype(int)
    chunk_slots = list(range(S - P, S))
    plens[chunk_slots] = [hi - 5, hi // 2 + 3][:P]  # ragged last chunks
    prompts = [rng.integers(3, cfg.vocab_size, int(n) + 1) for n in plens]
    use_k = eng._use_kernels
    packed = jax.jit(partial(paged_forward_packed, use_kernel=use_k),
                     static_argnums=(1,))
    wide = jax.jit(partial(paged_forward_window, use_kernel=use_k),
                   static_argnums=(1,))
    flush = jax.jit(flush_paged_window)

    def chunk_of(s, at, shift=0):
        row = np.zeros((C,), np.int32)
        part = prompts[s][at + shift:min(at + C, plens[s]) + shift]
        row[:len(part)] = part
        return row, len(part)

    with eng._mesh_ctx():
        # the S - P decoding slots prefill lane-wide, then one flush: the
        # fork both sides start from
        cache, win, wlen = eng.cache, eng._kv_window, eng._win_len
        tokens = np.zeros((S,), np.int32)
        dec = np.ones((S,), bool)
        dec[chunk_slots] = False
        for at in range(0, int(plens[dec].max()), C):
            rows = [chunk_of(s, at) for s in range(S)]
            cnt = np.array([n for _, n in rows]) * dec
            logits, win = wide(params, cfg, jnp.asarray(np.stack(
                [r for r, _ in rows])), cache, win, wlen,
                active=jnp.asarray(cnt > 0))
            ends = (cnt > 0) & (at + cnt >= plens)
            top = np.asarray(logits.argmax(-1))
            tokens = np.where(ends, top[np.arange(S), np.maximum(cnt - 1, 0)],
                              tokens)
            wlen = wlen + jnp.asarray(cnt, jnp.int32)
        cache, wlen, _ = flush(cache, win, wlen)

        def bump(wl):  # index_shift: the chunk slots start one entry late
            return wl.at[jnp.asarray(chunk_slots)].add(1)

        ref = (cache, win, wlen)
        sides = {f: (cache, win, bump(wlen) if f == "index_shift" else wlen)
                 for f in FAULTS}
        start = k // 2   # admitted mid-block: the prompt crosses a flush
        cursor = np.zeros((S,), np.int64)
        out = {f: {"decode": [], "chunk": [], "chunk_slot_after_flush": [],
                   "argmax_agree": 0, "rows": 0} for f in FAULTS}
        for step in range(BLOCKS * k):
            live = dec | (step >= start)
            is_pf = live & ~dec & (cursor < plens)
            pf = [s for s in range(S) if is_pf[s]][:P]
            rows = {s: chunk_of(s, cursor[s]) for s in pf}
            cnt = np.zeros((S,), np.int64)
            for s, (_, n) in rows.items():
                cnt[s] = n
            toks = np.broadcast_to(tokens[:, None], (S, C)).copy()
            for s, (row, _) in rows.items():
                toks[s] = row
            logits, rwin = wide(params, cfg, jnp.asarray(toks), ref[0],
                                ref[1], ref[2], active=jnp.asarray(live))
            col = np.where(is_pf, np.maximum(cnt - 1, 0), 0)
            want = np.asarray(logits, np.float32)[np.arange(S), col]
            adv = jnp.asarray(np.where(is_pf, cnt, live), jnp.int32)
            ref = (ref[0], rwin, ref[2] + adv)
            slot = np.array((pf + [0] * P)[:P], np.int32)
            for f in FAULTS:
                fc, fw, fl = sides[f]
                shift = 1 if f == "chunk_shift" else 0
                ctoks = np.stack([chunk_of(s, cursor[s], shift)[0]
                                  for s in slot])
                ccnt = np.array([cnt[s] if i < len(pf) else 0
                                 for i, s in enumerate(slot)], np.int32)
                got, fw, _ = packed(params, cfg, jnp.asarray(tokens), fc,
                                    jnp.asarray(ctoks), jnp.asarray(slot),
                                    jnp.asarray(ccnt),
                                    jnp.asarray(live & ~is_pf), fw, fl)
                sides[f] = (fc, fw, fl + adv)
                read = _reading(np.asarray(got, np.float32), want)
                o = out[f]
                o["decode"] += read[dec].tolist()
                o["chunk"] += read[is_pf].tolist()
                if step >= k:
                    o["chunk_slot_after_flush"] += read[live & ~dec].tolist()
                o["argmax_agree"] += int(
                    (np.asarray(got).argmax(-1) == want.argmax(-1))[live].sum())
                o["rows"] += int(live.sum())
            emit = live & (~is_pf | (cursor + cnt >= plens))
            tokens = np.where(emit, want.argmax(-1), tokens).astype(np.int32)
            cursor = cursor + cnt
            if (step + 1) % k == 0:   # the drain's flush, on every side
                c, wl, _ = flush(*ref)
                ref = (c, ref[1], wl)
                for f in FAULTS:
                    c, wl, _ = flush(*sides[f])
                    sides[f] = (c, sides[f][1], wl)

    def summary(o):
        res = {"argmax_agree": o["argmax_agree"], "rows": o["rows"]}
        for key in ("decode", "chunk", "chunk_slot_after_flush"):
            res[key + "_max"] = float(np.max(o[key]))
            res[key + "_median"] = float(np.median(o[key]))
        res["max"] = max(res["decode_max"], res["chunk_max"])
        return res

    res = {f: summary(o) for f, o in out.items()}
    res["chunk_width"], res["steps"] = C, BLOCKS * k
    res["chunk_prompts"] = [int(plens[s]) for s in chunk_slots]
    return res


def run_recurrent(cfg, params, sv, config, P, seed=29):
    """The packed step of a model with recurrent layers against its
    plain reference, for P chunks; returns readings per fault."""
    import jax
    import jax.numpy as jnp
    from butterfly_tpu.cache.paged import (
        flush_paged_window, paged_forward_packed)
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.quant.int8 import is_quantized_leaf
    from servebench.refcheck import leaf_reader, load_reference

    k, S = sv["decode_steps_per_tick"], sv["max_batch"]
    steps = BLOCKS * k
    probe = RuntimeConfig()
    C = min(probe.prefill_inline_budget, probe.prefill_chunk)
    hi = min(128, sv["max_seq"] // 3)
    # a pool of what the longest stream here holds, a slot
    per = -(-(max(hi, 2 * C + 6) + steps + C) // sv["page_size"])
    rt = RuntimeConfig(max_batch_size=S, max_seq_len=sv["max_seq"],
                       page_size=sv["page_size"], num_pages=S * per,
                       kv_quant=sv.get("kv_quant", "none"),
                       decode_steps_per_tick=k)
    rng = np.random.default_rng(seed)
    plens = np.exp(rng.uniform(np.log(hi / 4), np.log(hi), S)).astype(int)
    chunk_slots = list(range(S - P, S))
    plens[chunk_slots] = [2 * C + 6, C + C // 2 + 3][:P]
    seqs = [rng.integers(3, cfg.vocab_size, int(n) + steps + 1)
            for n in plens]
    checked = sorted({0, 1, *chunk_slots})
    reference = load_reference(config["reference"])
    leaf = leaf_reader(params, is_quantized_leaf)
    want = {s: np.asarray(reference.logits(
        seqs[s][:plens[s] + steps], leaf, config), np.float32)
        for s in checked}

    def one(fault):
        eng = ServingEngine(Model(cfg), params, rt)
        for s in range(S):
            eng.set_table_row(s, list(range(s * per, (s + 1) * per)))
        eng._ensure_window(k * C)
        eng._sync_table()
        packed = jax.jit(partial(paged_forward_packed,
                                 use_kernel=eng._use_kernels),
                         static_argnums=(1,),
                         donate_argnames=("window", "state"))
        flush = jax.jit(flush_paged_window)
        cache, win, wlen = eng.cache, eng._kv_window, eng._win_len
        state, eng._ssm_state, eng._kv_window = eng._ssm_state, None, None
        at = np.zeros((S,), np.int64)          # tokens each slot has seen
        o = {"decode": [], "chunk": [], "rows": 0, "argmax_agree": 0}

        def step(decoding, chunk):
            """decoding [S] bool; chunk [(slot, n)] up to P of them."""
            nonlocal win, wlen, state
            toks = np.array([seqs[s][at[s]] for s in range(S)], np.int32)
            slot = np.array(([s for s, _ in chunk] + [0] * P)[:P], np.int32)
            cnt = np.array(([n for _, n in chunk] + [0] * P)[:P], np.int32)
            shift = 1 if fault == "chunk_shift" else 0
            ctoks = np.zeros((P, C), np.int32)
            for i, (s, n) in enumerate(chunk):
                ctoks[i, :n] = seqs[s][at[s] + shift:at[s] + shift + n]
            got, win, _, state = packed(
                params, cfg, jnp.asarray(toks), cache, jnp.asarray(ctoks),
                jnp.asarray(slot), jnp.asarray(cnt), jnp.asarray(decoding),
                window=win, win_len=wlen, state=state)
            adv = decoding.astype(np.int64)
            for s, n in chunk:
                adv[s] += n
            wlen = wlen + jnp.asarray(adv, jnp.int32)
            at[:] = at + adv
            return np.asarray(got, np.float32)

        def drain():
            nonlocal cache, wlen
            cache, wlen, _ = flush(cache, win, wlen)

        with eng._mesh_ctx():
            # the decoding slots' prompts, P chunks a step, a flush
            # every k steps as the scheduler drains
            todo = [s for s in range(S) if s not in chunk_slots]
            n_steps = 0
            while todo:
                now = todo[:P]
                step(np.zeros((S,), bool),
                     [(s, int(min(C, plens[s] - at[s]))) for s in now])
                todo = [s for s in todo if at[s] < plens[s]]
                n_steps += 1
                if n_steps % k == 0:
                    drain()
            drain()
            dec = np.ones((S,), bool)
            dec[chunk_slots] = False
            start = k // 2  # admitted mid-block: the prompt crosses a flush
            for i in range(steps):
                pf = [s for s in chunk_slots
                      if i >= start and at[s] < plens[s]]
                chunk = [(s, int(min(C, plens[s] - at[s]))) for s in pf]
                decoding = dec | np.array(
                    [i >= start and s in chunk_slots and s not in pf
                     for s in range(S)])
                got = step(decoding, chunk)
                for s in checked:
                    if not (decoding[s] or s in pf) or at[s] < plens[s]:
                        continue    # a chunk that is not the prompt's last
                    read = float(_reading(got[s], want[s][at[s] - 1]))
                    o["chunk" if s in pf else "decode"].append(read)
                    o["argmax_agree"] += int(
                        got[s].argmax() == want[s][at[s] - 1].argmax())
                    o["rows"] += 1
                if (i + 1) % k == 0:
                    drain()
        return o

    def summary(o):
        res = {"argmax_agree": o["argmax_agree"], "rows": o["rows"]}
        for key in ("decode", "chunk"):
            res[key + "_max"] = float(np.max(o[key]))
            res[key + "_median"] = float(np.median(o[key]))
        res["max"] = max(res["decode_max"], res["chunk_max"])
        return res

    res = {f: summary(one(f)) for f in FAULTS[:2]}
    res["chunk_width"], res["steps"] = C, steps
    res["chunk_prompts"] = [int(plens[s]) for s in chunk_slots]
    res["against"] = "the plain reference " + config["reference"]
    res["checked_slots"] = checked
    return res


def check(config: dict, toy: bool = False) -> dict:
    import jax
    from butterfly_tpu.core.config import MeshConfig, ModelConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.quant.int8 import init_params_by_leaf
    from servebench.launcher import model_fields

    kind = str(jax.devices()[0].device_kind)
    if jax.default_backend() != "tpu" and not toy:
        raise SystemExit(f"no TPU here ({kind}): this is chip evidence; "
                         "--toy rehearses on the CPU and says so")
    cfg = ModelConfig(**model_fields(config))
    sv = config["serve"]
    tp = int(sv.get("tensor_parallel", 1))
    mesh = make_mesh(MeshConfig(tensor=tp), jax.devices()[:tp]) \
        if tp > 1 else None
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0),
                                 quant=sv.get("quant", "none"), mesh=mesh)
    limit = LIMITS.get(cfg.recurrent_kind, LIMIT)
    out = {"device": kind, "evidence": "cpu toy" if toy else "chip",
           "tensor_parallel": tp, "limit": limit}
    for P in (1, 2):
        out[f"P{P}"] = run_recurrent(cfg, params, sv, config, P) \
            if cfg.has_ssm else run(cfg, params, mesh, sv, P)
    out["ok"] = all(out[p]["clean"]["max"] < limit
                    < out[p]["chunk_shift"]["max"] for p in ("P1", "P2"))
    if not cfg.has_ssm:
        out["index_shift_seen"] = all(out[p]["index_shift"]["max"] > limit
                                      for p in ("P1", "P2"))
    return out


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--toy"]
    result = check(json.loads(Path(args[0]).read_text()),
                   toy="--toy" in sys.argv)
    Path(args[1]).parent.mkdir(parents=True, exist_ok=True)
    Path(args[1]).write_text(json.dumps(result))
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
