"""What holds a model with a learned sparse-attention indexer to its plain
reference PAST the selection, on the chip, at the published widths:

    python3 tools/sparse_parity.py <config.json> <out.json> [--toy]

A cell's own reference check (servebench/refcheck.py) feeds 16 tokens, so
it never reaches a context longer than `index_topk` (2,048), where the
indexer first leaves a position out. Here ONE stream of STREAM tokens goes
through the packed mixed step the server runs (tools/window_parity.py's
driver: chunks of C prompt tokens into the write-combined window, a flush
every k steps as the scheduler drains, then decode rows, each of which
attends only the rows it selected: cache/paged.py sparse_paged_attend,
by the masked read of its live pages where kernels are on, SLOTS x MAX_SEQ
being a table the kernel serves, else by the gather; a latent-attention
model with an indexer, GLM-5's family, through latent_paged_attend, its
selection over the cached LATENT rows; `decode_read` in the
output says which), and its logits are compared with the
configuration's reference computed in blocks of rows: every chunk's last column and every decode row, in two
groups: BEFORE position topk (its last quarter: nothing is left out yet)
and PAST 2 x topk - topk / 32 (4,032 at 2,048: half the context or more
is left out). Logits, not tokens; the reading is the rms of the
difference over the reference's spread, per row, as refcheck.py reads it.

Beside the clean run, two controls planted in the program: `select_all`
(every position is attended: the model without its indexer) and
`select_recent` (the last topk positions in place of the indexer's
choice: a sliding window). Each must read as the clean run BEFORE topk
and pass the limit past it. A reading means something only between the
clean run's and a control's, and the limit lies there: LIMIT (PERF.md,
PR 36, gives the readings it was set from) or the configuration's own
`parity_tolerance`, stated in its file beside ITS readings. The MEDIAN
over a group's rows is what is held to it: in bfloat16 a near-tie among
128 router logits flips an expert in some rows, and a near-tie at the
selection's edge swaps one of 2,048 attended rows for another, while a
wrong selection moves every row. The largest reading of each group is
reported beside it.

What the logits cannot see, a selection that is off by a row, is COUNTED
(PR 55): while a run's steps are traced, every selection of the program
(cache/paged.py _selection: by counting in ops/select_mask.py where
kernels are on) is held to the plain path's over the same scores
(models/common.py select_mask: lax.top_k and a running count) inside the
program, position for position, and the calls, the rows that differ and
the positions are tallied from the device (`selection` in the output).
The clean run and both controls must differ NOWHERE, at any layer or
step; a third fault, `threshold_ulp` (a threshold one ulp above the k-th
score: what ties at the cut is left out), must be counted in every row
past topk though its logits read as the clean run's.

The tool reports chip evidence and refuses to run without a TPU; `--toy`
(the CPU rehearsal of tests/test_keye.py) says so in its output.
"""
import contextlib
import json
import sys
from functools import partial
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tools"))

from window_parity import _reading, served_rows  # noqa: E402

#: rms of the difference over the reference's spread: a group of rows whose
#: median is above it is wrong. Set between the chip's two readings past
#: 2 x topk (PERF.md, PR 36): the clean run 0.0206, `select_all` 0.1234
#: (2.4 times of room on either side; `select_recent` reads 0.71). A
#: configuration whose own two readings lie elsewhere states its limit
#: beside them in its file (`parity_tolerance`, `parity_tolerance_why`,
#: as `reference_tolerance` states refcheck.py's); this is the limit of
#: a file that states none
LIMIT = 0.05
STREAM, SLOTS, MAX_SEQ = 4500, 8, 5120
#: decode rows behind the prompt
DECODE = 84
#: the clean run, the controls and the fault the count alone can see:
#: what sparse_paged_attend selects by
FAULTS = {"clean": "index", "select_all": "all", "select_recent": "recent",
          "threshold_ulp": "index"}
#: the two whose LOGITS must pass the limit past topk
CONTROLS = ("select_all", "select_recent")


#: what the steps' traces noted of their kernels (ops.note_kernel): which
#: read served the decode rows of the newest check
KERNELS: dict = {}
#: fault -> [selections made, rows of them that are not the plain
#: path's, positions that differ], tallied from inside the programs
SELECTIONS: dict = {}


def _tally(fault, rows, positions):
    held = SELECTIONS.setdefault(fault, [0, 0, 0])
    held[0] += 1
    held[1] += int(rows)
    held[2] += int(positions)


def _ulp_high(scores, valid, k, use_kernel):
    """The planted fault: what scores ABOVE the k-th score, the
    selection of a threshold one ulp too high."""
    import jax.numpy as jnp
    from jax import lax
    if scores.shape[-1] <= k:
        return valid
    s = jnp.where(valid, scores, -jnp.inf)
    return valid & (s > lax.top_k(s, k)[0][..., -1:])


def _held_to_plain(fault, real):
    """cache/paged.py _selection, its result unchanged (threshold_ulp:
    replaced), with the plain path's selection over the same scores
    beside it in the program and their difference tallied."""
    import jax
    import jax.numpy as jnp
    from butterfly_tpu.models.common import select_mask

    def selection(scores, valid, k, use_kernel):
        made = _ulp_high if fault == "threshold_ulp" else real
        got = made(scores, valid, k, use_kernel)
        off = jnp.sum((got != 0) != select_mask(scores, valid, k), axis=-1)
        jax.debug.callback(partial(_tally, fault), jnp.sum(off > 0),
                           jnp.sum(off))
        return got
    return selection


@contextlib.contextmanager
def planted(fault: str):
    """The program's selection replaced while a step is traced, and the
    trace's kernel notes kept (KERNELS)."""
    from butterfly_tpu.cache import paged
    from butterfly_tpu.ops import record_kernels
    # the selection over keys and values, and the one over latent rows
    # (a latent-attention model with an indexer: GLM-5's family)
    names = ("sparse_paged_attend", "latent_paged_attend", "_selection")
    real = [getattr(paged, n) for n in names]
    for n, fn in zip(names[:2], real):
        setattr(paged, n, partial(fn, select=FAULTS[fault]))
    paged._selection = _held_to_plain(fault, real[2])
    try:
        with record_kernels(KERNELS):
            yield
    finally:
        for n, fn in zip(names, real):
            setattr(paged, n, fn)


def check(config: dict, toy: bool = False, stream: int = STREAM,
          decode: int = DECODE, seed: int = 36) -> dict:
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
    limit = float(config.get("parity_tolerance", LIMIT))
    sv = config["serve"]
    rt = RuntimeConfig(
        max_batch_size=sv["max_batch"] if toy else SLOTS,
        max_seq_len=sv["max_seq"] if toy else MAX_SEQ,
        page_size=sv["page_size"], kv_quant=sv.get("kv_quant", "none"),
        decode_steps_per_tick=sv["decode_steps_per_tick"],
        prefill_inline_budget=sv.get("prefill_inline_budget", 32))
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    topk = cfg.index_topk
    past = 2 * topk - topk // 32
    if not 0 < past < stream - decode <= stream <= rt.max_seq_len:
        raise ValueError(
            f"a stream of {stream} tokens ({decode} of them decoded) must "
            f"pass position {past}, twice the model's index_topk {topk}, in "
            f"its prompt and fit max_seq {rt.max_seq_len}")
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0),
                                 quant=sv.get("quant", "none"))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, stream).astype(np.int32)
    n_prompt = (stream - decode) // C * C
    KERNELS.clear()
    SELECTIONS.clear()
    served = served_rows(cfg, params, rt, tokens, n_prompt,
                         faults={f: {} for f in FAULTS}, planted=planted)
    jax.effects_barrier()       # the last steps' tallies
    kernels = dict(KERNELS)
    pos = np.asarray(served["clean"][0])
    before = (pos >= topk * 3 // 4) & (pos < topk)
    after = pos >= past
    keep = np.flatnonzero(before | after)
    want = np.asarray(load_reference(config["reference"]).logits(
        tokens, leaf_reader(params, is_quantized_leaf), config,
        rows=pos[keep].tolist()), np.float32)
    after = after[keep]
    out = {"device": kind, "evidence": "cpu toy" if toy else "chip",
           "limit": limit, "stream": int(stream),
           "prompt": int(n_prompt), "chunk_width": C, "index_topk": topk,
           "past": past, "rows_before": int((~after).sum()),
           "rows_after": int(after.sum()), "kernels": kernels,
           "decode_read": "masked (ops/sparse_attention.py)" if any(
               k.startswith("sparse_attention") for k in kernels)
           else "masked (ops/latent_attention.py latent_select_attention)"
           if any(k.startswith("latent_select") for k in kernels)
           else "gather"}
    for fault, (_, got) in served.items():
        read = _reading(got[keep], want)
        out[fault] = {
            f"{group}_{stat}": float(fn(read[sel]))
            for group, sel in (("before", ~after), ("after", after))
            for stat, fn in (("max", np.max), ("median", np.median))}
        out[fault]["argmax_agree"] = int(
            (got[keep].argmax(-1) == want.argmax(-1)).sum())
        out[fault]["rows"] = [round(float(r), 4) for r in read]
    out["positions"] = pos[keep].tolist()
    out["selection"] = {
        f: dict(zip(("calls", "rows_differ", "positions_differ"), held))
        for f, held in SELECTIONS.items()}
    clean = out["clean"]
    out["ok"] = bool(
        max(clean["before_median"], clean["after_median"]) < limit
        and all(out[f]["before_median"] < limit < out[f]["after_median"]
                for f in CONTROLS)
        and all(out["selection"][f]["calls"] > 0
                and (out["selection"][f]["rows_differ"] > 0)
                == (f == "threshold_ulp") for f in FAULTS))
    return out


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--toy"]
    result = check(json.loads(Path(args[0]).read_text()),
                   toy="--toy" in sys.argv)
    Path(args[1]).parent.mkdir(parents=True, exist_ok=True)
    Path(args[1]).write_text(json.dumps(result))
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
