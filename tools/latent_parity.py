"""What holds a latent-attention model's served programs to its plain
reference over a LONG stream, on the chip, at the published widths:

    python3 tools/latent_parity.py <config.json> <out.json> [--toy]

A cell's own reference check (servebench/refcheck.py) feeds 16 tokens
through the contiguous cache: it holds the layer's arithmetic (the
absorbed products against the reference's expanded form, the router, the
dense layer) and never reaches the paged pool, the window and its flush,
the Pallas read over pages, or a context of thousands of rows. Here ONE
stream of STREAM tokens goes through the packed mixed step the server
runs (tools/window_parity.py's driver, over the engine's own pool,
window and weights: chunks of C prompt tokens staged in the
write-combined window, a flush every k steps as the scheduler drains,
each chunk's columns attending their stream's cached rows ABSORBED
through the gathered view, then decode rows, each of which reads its
pages through ops/latent_attention.py: nine chunks of 512 rows and the
window at position 4,500), and its logits are compared with the
configuration's reference (the EXPANDED form, every head's keys and
values materialised, in blocks of query rows so that it fits): every
chunk's last column PAST position PAST and every decode row. Logits, not
tokens; the reading is the rms of the difference over the reference's
spread, per row, as refcheck.py reads it.

Beside the clean run, two faults planted in the program: `pages_astray`
(a decode row's walk of its block table goes astray past PAST rows:
every later entry names the null page, as a wrong page index in the
kernel's copies would. The chunks' columns, which read through XLA's
gather of the true table, stay clean; the decode rows must pass LIMIT) and
`chunk_blind` (a chunk's columns attend their own chunk and nothing
cached, the form a prefill WITHOUT a cached context may take, misapplied:
the rows such a chunk caches in the deeper layers are wrong too, so
every row, a decode row's as well, must pass LIMIT). A reading means something
only between the clean run's and a fault's, and LIMIT lies there
(PERF.md, PR 44, gives the readings it was set from). The MEDIAN over a
group's rows is what is held to it: in bfloat16 a near-tie among 256
router scores flips an expert in some rows, while a wrong read moves
every row. The largest reading of each group is reported beside it.

The tool reports chip evidence and refuses to run without a TPU; `--toy`
(the CPU rehearsal of tests/test_joyai.py) says so in its output.
"""
import contextlib
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tools"))

from window_parity import _reading, served_rows  # noqa: E402

#: rms of the difference over the reference's spread: a group of rows
#: whose median is above it is wrong. Set between the chip's readings
#: (PERF.md, PR 44): the clean run's medians 0.0105 and 0.0106,
#: `pages_astray` 0.395 in the decode rows, `chunk_blind` 0.566 and 1.40:
#: the geometric middle of 0.0106 and 0.395, six times of room either side
LIMIT = 0.06
STREAM, SLOTS, MAX_SEQ = 4500, 8, 5120
#: decode rows behind the prompt
DECODE = 84
#: rows of the pool a decode row still finds under `pages_astray`, and
#: the position past which a chunk's last column is compared
PAST = 4096
FAULTS = ("clean", "pages_astray", "chunk_blind")


@contextlib.contextmanager
def planted(fault: str, past: int):
    """The fault planted in the program while a step is traced."""
    import jax.numpy as jnp
    from butterfly_tpu.cache import paged
    real_attend = paged.latent_paged_attend

    def pages_astray(q, kp, layer, *, page_table, **kw):
        # a decode row's walk of its table goes astray past `past` rows:
        # every later entry names the null page. The kernel and the jnp
        # read both follow the table, so both read the wrong rows
        if q.shape[1] == 1:
            at = jnp.arange(page_table.shape[1])[None, :] * kp.shape[3]
            page_table = jnp.where(at >= past, kp.shape[1] - 1, page_table)
        return real_attend(q, kp, layer, page_table=page_table, **kw)

    def chunk_blind(q, kp, layer, *, positions, mask, **kw):
        # a chunk's columns attend their own chunk and nothing cached,
        # as a prefill WITHOUT a cached context may: the form misapplied
        # to a chunk that has one
        if q.shape[1] > 1:
            at = jnp.arange(mask.shape[-1])[None, None, :]
            mask = mask & (at >= positions[:, :1, None])
        return real_attend(q, kp, layer, positions=positions, mask=mask,
                           **kw)

    if fault == "pages_astray":
        paged.latent_paged_attend = pages_astray
    elif fault == "chunk_blind":
        paged.latent_paged_attend = chunk_blind
    try:
        yield
    finally:
        paged.latent_paged_attend = real_attend


def check(config: dict, toy: bool = False, stream: int = STREAM,
          decode: int = DECODE, past: int = PAST, seed: int = 44) -> dict:
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
    if not cfg.is_latent:
        raise ValueError("the configuration has no latent attention "
                         "(kv_lora_rank)")
    sv = config["serve"]
    rt = RuntimeConfig(
        max_batch_size=sv["max_batch"] if toy else SLOTS,
        max_seq_len=sv["max_seq"] if toy else MAX_SEQ,
        page_size=sv["page_size"], kv_quant=sv.get("kv_quant", "none"),
        decode_steps_per_tick=sv["decode_steps_per_tick"],
        prefill_inline_budget=sv.get("prefill_inline_budget", 32))
    C = min(rt.prefill_inline_budget, rt.prefill_chunk)
    if not 0 < past < stream - decode <= stream <= rt.max_seq_len:
        raise ValueError(
            f"a stream of {stream} tokens ({decode} of them decoded) must "
            f"pass position {past} in its prompt and fit max_seq "
            f"{rt.max_seq_len}")
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0),
                                 quant=sv.get("quant", "none"))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, stream).astype(np.int32)
    n_prompt = (stream - decode) // C * C
    served = served_rows(
        cfg, params, rt, tokens, n_prompt, faults={f: {} for f in FAULTS},
        planted=lambda fault: planted(fault, past))
    pos = np.asarray(served["clean"][0])
    decoded = pos >= n_prompt
    keep = np.flatnonzero(decoded | (pos >= past))
    want = np.asarray(load_reference(config["reference"]).logits(
        tokens, leaf_reader(params, is_quantized_leaf), config,
        rows=pos[keep].tolist()), np.float32)
    decoded = decoded[keep]
    out = {"device": kind, "evidence": "cpu toy" if toy else "chip",
           "limit": LIMIT, "stream": int(stream), "prompt": int(n_prompt),
           "chunk_width": C, "past": past,
           "rows_chunks": int((~decoded).sum()),
           "rows_decoded": int(decoded.sum())}
    for fault, (_, got) in served.items():
        read = _reading(got[keep], want)
        out[fault] = {
            f"{group}_{stat}": float(fn(read[sel]))
            for group, sel in (("chunks", ~decoded), ("decoded", decoded))
            for stat, fn in (("max", np.max), ("median", np.median))}
        out[fault]["argmax_agree"] = int(
            (got[keep].argmax(-1) == want.argmax(-1)).sum())
        out[fault]["rows"] = [round(float(r), 4) for r in read]
    out["positions"] = pos[keep].tolist()
    clean, astray, blind = (out[f] for f in FAULTS)
    out["ok"] = bool(
        max(clean["chunks_median"], clean["decoded_median"]) < LIMIT
        and astray["chunks_median"] < LIMIT < astray["decoded_median"]
        and min(blind["chunks_median"], blind["decoded_median"]) > LIMIT)
    return out


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--toy"]
    result = check(json.loads(Path(args[0]).read_text()),
                   toy="--toy" in sys.argv)
    Path(args[1]).parent.mkdir(parents=True, exist_ok=True)
    Path(args[1]).write_text(json.dumps(result))
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
