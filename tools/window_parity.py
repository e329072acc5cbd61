"""What holds a sliding-window model's served programs to its plain
reference PAST the window, on the chip, at the published widths:

    python3 tools/window_parity.py <config.json> <out.json> [--toy]

A cell's own reference check (servebench/refcheck.py) feeds 16 tokens and
a `batch` stream stays under 400, so neither reaches a window of 4,096.
Here ONE stream of STREAM tokens goes through the packed mixed step the
server runs (cache/paged.py paged_forward_packed: chunks of C prompt tokens
into the write-combined window, a flush every k steps as the scheduler
drains, then decode rows through the paged kernel), with room for it
(`max_seq` over the stream, a few slots), and its logits are compared with
the configuration's reference computed in blocks of rows
(`logits(..., rows=...)`): every chunk's last column and every decode
row, in two groups: BEFORE the position where the window first binds
(the last quarter of the window) and AFTER it (a sixteenth of the window
past it and further: at 4,096, where 256 keys or more should have been
dropped). Logits, not tokens; the reading is the rms of the difference
over the reference's spread, per row, as refcheck.py reads it.

Beside the clean run, two planted faults on the program's side, each the
same weights under a ModelConfig one layout away: `window_off` (no layer
slides: reads as the clean run BEFORE, and must pass LIMIT AFTER) and
`rope_everywhere` (the full layers rotate too: must pass LIMIT in both
groups). A reading means something only between the clean run's and a
fault's, and LIMIT lies there (PERF.md, PR 34, gives the readings it was
set from). The MEDIAN over a group's rows is what is held to it: in
bfloat16 a near-tie among 64 router logits flips an expert in about one
row in four and sets that row's reading (0.015-0.05 where its neighbours
read 0.006), while a wrong mask or rotation moves every row. The largest
reading of each group is reported beside it.

A file may state its own: `parity_stream` (the stream's length: a model
whose sliding layers keep their rows in a RING, cache/paged.py
ring_pages, states one past two windows and more, so that every entry
of the ring is rewritten twice; a third group of rows, `after2`, past
TWO windows, is then read beside the two), `parity_controls` (two of
FAULTS by name, each of which must read as the clean run BEFORE and
pass the limit AFTER unless it is `rope_everywhere`: `all_slide`, the
full layers cut to the window too, beside `window_off`) and
`parity_tolerance` (the limit, with the readings it was set from in
`parity_tolerance_why`). Each run has an engine, and so a cache, of its
own: a fault's ModelConfig decides which layers' rows go where.

The tool reports chip evidence and refuses to run without a TPU; `--toy`
(the CPU rehearsal of tests/test_smallthinker.py) says so in its output.
"""
import json
import sys
from functools import partial
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

#: rms of the difference over the reference's spread: a group of rows whose
#: median is above it is wrong
LIMIT = 0.03
STREAM, SLOTS, MAX_SEQ = 4500, 8, 5120
#: decode rows behind the prompt
DECODE = 84
FAULTS = {"clean": {}, "window_off": {"sliding_window_layout": 0},
          "rope_everywhere": {"rope_layout": 1},
          "all_slide": {"sliding_window_layout": 1}}
#: the controls of a file that names none
CONTROLS = ("window_off", "rope_everywhere")


def _reading(a, b):
    """Per row: rms(a - b) over the spread of b."""
    return np.sqrt(((a - b) ** 2).mean(-1)) / b.std(-1)


def served_rows(cfg, params, rt, tokens, n_prompt, faults=None, planted=None):
    """The stream through the packed step under each of `faults` (FAULTS:
    {fault: the layouts it sets}): {fault: ([positions], logits
    [rows, V])}, a chunk's last column and each decode row. planted
    (tools/sparse_parity.py): fault -> a context manager that plants it
    in the program while the fault's step is traced and run."""
    import contextlib

    import jax
    import jax.numpy as jnp
    from butterfly_tpu.cache.paged import (
        flush_paged_window, paged_forward_packed)
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.models.common import Model

    k, S = rt.decode_steps_per_tick, rt.max_batch_size
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    per = rt.max_seq_len // rt.page_size
    flush = jax.jit(flush_paged_window)
    L = cfg.num_layers
    out = {}
    for fault, layouts in ({f: FAULTS[f] for f in ("clean",) + CONTROLS}
                           if faults is None else faults).items():
        fcfg = cfg.replace(**{name: (v,) * L for name, v in layouts.items()})
        # the engine as the factory of the state: pool, window and weights
        # in their layout, the kernels' switch. One a fault: which layers'
        # rows lie in a ring is the fault's ModelConfig's to say
        eng = ServingEngine(Model(fcfg), params, rt)
        for s in range(S):
            eng.set_table_row(s, list(range(s * per, (s + 1) * per)))
        eng._ensure_window(k * C)
        eng._sync_table()
        with eng._mesh_ctx():
            # a step of its own for each fault: what is planted is traced
            packed = jax.jit(partial(paged_forward_packed,
                                     use_kernel=eng._use_kernels),
                             static_argnums=(1,))
            cache, win, wlen = eng.cache, eng._kv_window, eng._win_len
            pos, rows = [], []
            chain = np.zeros((S,), np.int32)
            idle = jnp.zeros((S,), bool)
            steps = [(at, C) for at in range(0, n_prompt, C)] \
                + [(at, 0) for at in range(n_prompt, len(tokens))]
            for i, (at, n) in enumerate(steps):
                if n:       # a chunk of slot 0's prompt, no slot decodes
                    chunk, active = tokens[None, at:at + C], idle
                else:       # slot 0 decodes, the chunk is empty
                    chain[0] = tokens[at]
                    chunk, active = np.zeros((1, C), np.int32), idle.at[0].set(True)
                with planted(fault) if planted else contextlib.nullcontext():
                    got, win, _ = packed(
                        params, fcfg, jnp.asarray(chain), cache,
                        jnp.asarray(chunk), jnp.asarray([0]),
                        jnp.asarray([n], jnp.int32), active, win, wlen)
                wlen = wlen.at[0].add(n or 1)
                pos.append(at + max(n, 1) - 1)
                rows.append(np.asarray(got[0], np.float32))
                if (i + 1) % k == 0:    # the drain's flush
                    cache, wlen, _ = flush(cache, win, wlen)
            out[fault] = (pos, np.stack(rows))
        del eng, cache, win
    return out


def check(config: dict, toy: bool = False, stream: int = 0,
          decode: int = DECODE, seed: int = 34) -> dict:
    import jax
    from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
    from butterfly_tpu.quant.int8 import init_params_by_leaf, is_quantized_leaf
    from servebench.launcher import model_fields
    from servebench.refcheck import leaf_reader, load_reference

    kind = str(jax.devices()[0].device_kind)
    if jax.default_backend() != "tpu" and not toy:
        raise SystemExit(f"no TPU here ({kind}): this is chip evidence; "
                         "--toy rehearses on the CPU and says so")
    cfg = ModelConfig(**model_fields(config))
    sv = config["serve"]
    stream = stream or int(config.get("parity_stream", STREAM))
    limit = float(config.get("parity_tolerance", LIMIT))
    controls = tuple(config.get("parity_controls", CONTROLS))
    rt = RuntimeConfig(
        max_batch_size=sv["max_batch"] if toy else SLOTS,
        max_seq_len=sv["max_seq"] if toy
        else max(MAX_SEQ, -(-(stream + 64) // 512) * 512),
        page_size=sv["page_size"], kv_quant=sv.get("kv_quant", "none"),
        decode_steps_per_tick=sv["decode_steps_per_tick"],
        prefill_inline_budget=sv.get("prefill_inline_budget", 32))
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    if not cfg.sliding_window < stream - decode <= stream <= rt.max_seq_len:
        raise ValueError(
            f"a stream of {stream} tokens ({decode} of them decoded) must "
            f"pass the model's window of {cfg.sliding_window} in its prompt "
            f"and fit max_seq {rt.max_seq_len}")
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0),
                                 quant=sv.get("quant", "none"))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, stream).astype(np.int32)
    n_prompt = (stream - decode) // C * C
    served = served_rows(cfg, params, rt, tokens, n_prompt,
                         {f: FAULTS[f] for f in ("clean",) + controls})
    pos = np.asarray(served["clean"][0])
    window = cfg.sliding_window
    # compare where the window is about to bind, and where it has bound
    before = (pos >= window * 3 // 4) & (pos < window)
    after = pos >= window + window // 16
    keep = np.flatnonzero(before | after)
    want = np.asarray(load_reference(config["reference"]).logits(
        tokens, leaf_reader(params, is_quantized_leaf), config,
        rows=pos[keep].tolist()), np.float32)
    after = after[keep]
    # past TWO windows every entry of a ring has been rewritten twice
    after2 = pos[keep] >= 2 * window + window // 16
    out = {"device": kind, "evidence": "cpu toy" if toy else "chip",
           "limit": limit, "stream": int(stream),
           "rows_after2": int(after2.sum()), "controls": list(controls),
           "prompt": int(n_prompt), "chunk_width": C,
           "sliding_window": cfg.sliding_window,
           "rows_before": int((~after).sum()), "rows_after": int(after.sum())}
    for fault, (_, got) in served.items():
        read = _reading(got[keep], want)
        out[fault] = {
            f"{group}_{stat}": float(fn(read[sel]))
            for group, sel in (("before", ~after), ("after", after),
                               ("after2", after2))
            for stat, fn in (("max", np.max), ("median", np.median))
            if sel.any()}
        out[fault]["argmax_agree"] = int(
            (got[keep].argmax(-1) == want.argmax(-1)).sum())
        out[fault]["rows"] = [round(float(r), 4) for r in read]
    out["positions"] = pos[keep].tolist()
    # the clean run under the limit in every group; a control that
    # changes the mask alone reads as the clean run before the window
    # binds and passes the limit behind it; one that rotates the full
    # layers passes it everywhere
    ok = all(v < limit for key, v in out["clean"].items()
             if key.endswith("_median"))
    for name in controls:
        c = out[name]
        ok = ok and (min(c["before_median"], c["after_median"]) > limit
                     if name == "rope_everywhere"
                     else c["before_median"] < limit < c["after_median"])
    out["ok"] = bool(ok)
    return out


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--toy"]
    result = check(json.loads(Path(args[0]).read_text()),
                   toy="--toy" in sys.argv)
    Path(args[1]).parent.mkdir(parents=True, exist_ok=True)
    Path(args[1]).write_text(json.dumps(result))
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
