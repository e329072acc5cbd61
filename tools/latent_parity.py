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

For a model whose residual path is n streams (`hc_mult`, Xing4.0's
family: tests/test_xing.py) the served logits do NOT hold the mixing's
arithmetic: in bfloat16 it is as exact as the bfloat16 streams it
mixes, and at the seeded leaves (alpha 0.01) the Sinkhorn has converged
before its last round (PERF.md, PR 49: the same stream served with
either read 0.0129 and 0.0123 beside the clean 0.0119). So the
mixing's COEFFICIENTS are held themselves (`mixing`, part of `ok`): the
program's `stream_read` as the chip compiles it, over MIX_ROWS rows of n
streams at the published widths, against the reference's `mixing` in
float32, H_res and H_post entry by entry, the largest difference over
the rows. The leaves are layer 0's, with `alpha_res` raised to
MIX_ALPHA_RES: each row's 4 x 4 logits then spread over +-10 and the
rounds have NOT converged by the last, as a trained model's need not.
Beside the clean reading, the mixing's arithmetic in bfloat16
(`mix_bfloat16`) and one round fewer (`sinkhorn_19`): both must pass
MIX_LIMIT, the clean reading stay under it.

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
#: the mixing's coefficients, for a model of n residual streams: the
#: clean program and its two faults
MIXING = ("clean", "mix_bfloat16", "sinkhorn_19")
#: rows of n streams the coefficients are read over, and the alpha_res
#: they are read at (the seeded .01 leaves every row the sublayer's b)
MIX_ROWS, MIX_ALPHA_RES = 1024, 2.0
#: largest difference of an entry of H_res or H_post / 2 from the
#: reference's. Set between the chip's readings (PERF.md, PR 49): clean
#: 1.58e-6, 19 rounds 4.42e-3, a bfloat16 mixing 1.09e-2: 190 times over
#: the first, 15 under the second
MIX_LIMIT = 3e-4


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

    from butterfly_tpu.models import common
    real_mix = common._MIX
    if fault == "pages_astray":
        paged.latent_paged_attend = pages_astray
    elif fault == "chunk_blind":
        paged.latent_paged_attend = chunk_blind
    elif fault == "mix_bfloat16":
        common._MIX = jnp.bfloat16
    try:
        yield
    finally:
        paged.latent_paged_attend = real_attend
        common._MIX = real_mix


def mixing_readings(cfg, params, config: dict, tokens) -> dict:
    """{fault: largest difference of a mixing coefficient from the
    reference's} for the program as it is and with each fault of MIXING
    planted (the module's head says over what)."""
    import jax
    import jax.numpy as jnp
    from butterfly_tpu.models import common
    from servebench.refcheck import load_reference

    n, R = cfg.hc_mult, min(MIX_ROWS, len(tokens) // cfg.hc_mult)
    # n streams of R rows as the program carries them: cfg.dtype
    x = jnp.take(params["embed"]["tok"], jnp.asarray(tokens[:n * R]), axis=0
                 ).reshape(n, 1, R, -1).astype(cfg.dtype)
    hc = {k: v[0] for k, v in params["layers"]["hc1"].items()}
    hc["alpha"] = hc["alpha"].at[2].set(MIX_ALPHA_RES)
    lp = {"ln1": jax.tree_util.tree_map(lambda v: v[0],
                                        params["layers"]["ln1"]), "hc1": hc}
    f32 = {k: jnp.asarray(v, jnp.float32) for k, v in hc.items()}
    with jax.default_matmul_precision("highest"):
        _, post, res = load_reference(config["reference"]).mixing(
            jnp.moveaxis(x[:, 0], 0, 1).astype(jnp.float32), f32["phi"],
            f32["b"], f32["alpha"], config)
    want = jnp.concatenate([jnp.moveaxis(res, 0, -1).reshape(n * n, R),
                            post.T / 2.0])
    out = {}
    for fault in MIXING:
        c = cfg.replace(hc_sinkhorn_iters=cfg.hc_sinkhorn_iters - 1) \
            if fault == "sinkhorn_19" else cfg
        with planted(fault, 0):
            _, (m, h_post) = jax.jit(
                lambda x, lp, c=c: common.stream_read(x, lp, 1, c))(x, lp)
        got = jnp.concatenate([m.reshape(n * n, R), h_post / 2.0])
        out[fault] = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    return out


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
    if cfg.hc_mult:
        mix = out["mixing"] = dict(
            mixing_readings(cfg, params, config, tokens), limit=MIX_LIMIT,
            rows=min(MIX_ROWS, stream // cfg.hc_mult),
            alpha_res=MIX_ALPHA_RES)
        out["ok"] = bool(out["ok"] and mix["clean"] < MIX_LIMIT < min(
            mix["mix_bfloat16"], mix["sinkhorn_19"]))
    return out


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--toy"]
    result = check(json.loads(Path(args[0]).read_text()),
                   toy="--toy" in sys.argv)
    Path(args[1]).parent.mkdir(parents=True, exist_ok=True)
    Path(args[1]).write_text(json.dumps(result))
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
