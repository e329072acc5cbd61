"""The reference check's decode calls of `decode_width` positions
(servebench/refcheck.py) on the CPU at a toy's size: which rows are
compared at each width, that a wide call of the causal program agrees
with the causal reference, that the check tells a block mask from a
causal one, and that a file without the key is checked as the parent
checked it, to the last bit.
"""
import json
import os
import random
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import peaks, refcheck  # noqa: E402
from servebench.launcher import model_fields, serve_argv  # noqa: E402
from servebench.manifest import decode_width, load_manifest  # noqa: E402

FILES = "tests/servebench/files"
PATHS = ["servebench", FILES]
#: the two files whose check must stay the parent's, by name: a later
#: configuration of the manifest is held to `test_every_configuration_
#: of_the_manifest_can_be_checked`, not to Mistral's width
MISTRAL = ["servebench/configs/mistral-7b-v0.3.json",
           "servebench/configs/mistral-7b-v0.3-bf16-tp4.json"]
MANIFEST = load_manifest(ROOT)
SEEDS = [2 ** 31 + 5, 7, 11]


def toy(name="tiny-llama-w4", **over):
    config = json.loads((ROOT / FILES / "configs" / (name + ".json")).read_text())
    return dict(config, **over)


def row_errors(b, toks, config):
    """{position: rms error} of the program's rows against the reference."""
    want = b.ref.logits(toks, b.leaf, config)
    return {j: refcheck.errors([(j, g)], want)[0]
            for j, g in refcheck.program_rows(b, toks)}


def test_the_key_is_the_benchmarks_alone():
    """`decode_width` sits at the top level of the file: the program's
    ModelConfig fields and the flags of `butterfly serve` are those of
    the same file without it."""
    wide, plain = toy(), toy("tiny-llama")
    assert {k: v for k, v in wide.items() if k not in (
        "name", "source", "decode_width", "reference_tolerance",
        "reference_tolerance_why")} == {
            k: v for k, v in plain.items() if k not in ("name", "source")}
    assert model_fields(wide) == model_fields(plain)
    assert serve_argv(wide, 1)[3:] == serve_argv(plain, 1)[3:]
    assert not [a for a in serve_argv(wide, 1) if "width" in a]
    assert "decode_width" not in wide["serve"]


@pytest.mark.parametrize("width, positions", [
    (1, [11, 12, 13, 14, 15]),            # a prefill of 12, four calls of 1
    (2, [11, 12, 13, 14, 15]),            # a prefill of 12, two calls of 2
    (4, [11] + list(range(12, 20)))])     # a prefill of 12, two calls of 4
def test_rows_and_positions_at_each_width(width, positions):
    config = toy(decode_width=width)
    b = refcheck.build(config, paths=PATHS)
    assert b.width == width
    toks = refcheck.sample(3, b.cfg.vocab_size, width)
    assert [len(t) for t in toks] == [positions[-1] + 1] * refcheck.PROMPTS
    for t in toks:
        err = row_errors(b, t, config)
        assert list(err) == positions
        # the causal program, fed `width` positions a call, against the
        # toy's causal reference: float32 on both sides
        assert max(err.values()) < 1e-5 < config["reference_tolerance"]


@pytest.mark.parametrize("seed", SEEDS)
def test_wide_call_of_the_causal_program_agrees_at_all_nine_positions(seed):
    config = toy()
    assert config["decode_width"] == 4 and config["reference"] == "llama_f32"
    b = refcheck.build(config, paths=PATHS)
    for t in refcheck.sample(seed, b.cfg.vocab_size, b.width):
        err = row_errors(b, t, config)
        assert len(err) == 9 and max(err.values()) < 1e-5


@pytest.mark.parametrize("seed", SEEDS)
def test_the_check_tells_a_block_mask_from_a_causal_one(seed):
    """The unchanged causal program at width 4 against the toy's
    reference under the block mask `j // 4 <= p // 4`: NOT ok, by ten
    times the limit or more, at EVERY row compared. With two layers even
    the last row of a block (11, 15, 19) differs, though it attends the
    same positions under both masks: from the second layer on, the keys
    and values of the rows before it were computed under the other mask."""
    config = toy(reference="toyblock_f32")
    limit = config["reference_tolerance"]
    b = refcheck.build(config, paths=PATHS)
    for t in refcheck.sample(seed, b.cfg.vocab_size, b.width):
        err = row_errors(b, t, config)
        assert len(err) == 9 and min(err.values()) > 10 * limit, err
        rms, _ = refcheck.errors(refcheck.program_rows(b, t),
                                 b.ref.logits(t, b.leaf, config))
        assert not rms <= limit and rms == max(err.values())


def test_with_one_layer_only_the_last_row_of_a_block_agrees():
    """What the mask alone does: through ONE layer the last row of a
    block reads the same under both masks (it attends j <= p either
    way), and every other row does not."""
    config = toy(reference="toyblock_f32", num_hidden_layers=1)
    limit = config["reference_tolerance"]
    b = refcheck.build(config, paths=PATHS)
    for t in refcheck.sample(5, b.cfg.vocab_size, b.width):
        err = row_errors(b, t, config)
        last = {j: e for j, e in err.items() if j % 4 == 3}
        assert sorted(last) == [11, 15, 19] and max(last.values()) < 1e-5
        assert min(e for j, e in err.items() if j not in last) > 10 * limit


@pytest.mark.parametrize("width", [0, -1, True, 2.0, "4", None])
def test_a_width_under_one_or_no_whole_number_is_an_error_that_names_the_key(width):
    config = toy(decode_width=width)
    for read in (decode_width, lambda c: refcheck.build(c, paths=PATHS),
                 lambda c: peaks.block_least_seconds(c, "TPU v5 lite", 1, 4,
                                                     [0] * 32)):
        with pytest.raises(ValueError, match="`decode_width` of 'tiny-llama-w4'"):
            read(config)


def test_a_width_that_does_not_fit_the_checks_cache_is_an_error_that_names_the_key():
    # 10: a prefill of 10 and two calls of 10 fill 30 of the 32 positions
    assert refcheck.lengths(10) == (10, 20) and refcheck.CACHE == 32
    assert len(refcheck.sample(1, 512, 10)[0]) == 30
    for width in (11, 12, 16, 64):
        with pytest.raises(ValueError, match="`decode_width`.*cache of 32"):
            refcheck.build(toy(decode_width=width), paths=PATHS)
    # the least time of a block has no cache to fit
    assert peaks.block_least_seconds(
        toy(decode_width=64), "TPU v5 lite", 1, 4, [0] * 32)["flops"] > 0


def test_refcheck_as_the_harness_starts_it_on_the_wide_toy(tmp_path):
    out = tmp_path / "refcheck.json"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / ".jax_cache"))
    r = subprocess.run(
        [sys.executable, str(ROOT / "servebench" / "refcheck.py"), "--config",
         str(ROOT / FILES / "configs" / "tiny-llama-w4.json"), "--seed",
         str(2 ** 31 + 77), "--out", str(out)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(out.read_text())
    assert got["ok"] and got["rms_err"] < 1e-5 and got["tolerance"] == 1e-4
    assert got["positions"] == 18 and got["decode_width"] == 4
    assert got["reference"] == "llama_f32"


# --- what must not move: a file without the key is the parent's check ---

def parent_sample(seed, vocab):
    """`sample` as the parent had it (PR 26), written out."""
    rng = random.Random(int(seed))
    return [[rng.randrange(1, vocab) for _ in range(12 + 4)] for _ in range(2)]


def parent_program_rows(b, toks):
    """`program_rows` as the parent had it (PR 26), written out: a
    prefill of 12, then one token a call, the last row of each."""
    import jax.numpy as jnp
    cache = b.model.init_cache(1, 32)
    got, cache = b.fwd(b.params, jnp.asarray([toks[:12]], jnp.int32), cache)
    rows = [(11, got[0, -1])]
    for j in range(12, len(toks)):
        got, cache = b.fwd(b.params, jnp.asarray([[toks[j]]], jnp.int32), cache)
        rows.append((j, got[0, -1]))
    return rows


@pytest.mark.parametrize("file", MISTRAL)
def test_mistral_files_are_sampled_as_the_parent_did(file):
    assert file in [c["file"] for c in MANIFEST["configs"]]
    config = json.loads((ROOT / file).read_text())
    assert "decode_width" not in config and decode_width(config) == 1
    assert refcheck.lengths(1) == (12, 4)
    for seed in (0, 41, 2 ** 31 + 2600):
        assert refcheck.sample(seed, config["vocab_size"]) == \
            refcheck.sample(seed, config["vocab_size"], decode_width(config)) == \
            parent_sample(seed, config["vocab_size"])


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_every_configuration_of_the_manifest_can_be_checked(cfg):
    """Whatever its family and width: the width is a whole number of 1
    or more, a prefill and two calls of it fit the check's cache, and
    the reference it names lies under one of the manifest's `paths`."""
    config = json.loads((ROOT / cfg["file"]).read_text())
    w = decode_width(config)            # raises unless a whole number, 1 or more
    prefill, decode = refcheck.lengths(w)       # raises unless they fit
    assert prefill % w == 0 and decode >= 2 * w
    assert prefill + decode <= refcheck.CACHE
    assert len(refcheck.sample(2 ** 31 + 5, config["vocab_size"], w)[0]) == \
        prefill + decode
    assert callable(refcheck.load_reference(config["reference"]).logits)
    assert float(config.get("reference_tolerance", refcheck.TOLERANCE)) > 0


def test_a_file_without_the_key_is_driven_as_the_parent_did():
    """The toy of the Mistral files' family, whose file has no key
    either: the same ten positions, every row the parent's to the last
    bit."""
    import jax.numpy as jnp
    family = toy("tiny-llama")
    assert family["model_type"] == "llama" and "decode_width" not in family
    b = refcheck.build(family, paths=PATHS)
    sample = refcheck.sample(41, b.cfg.vocab_size, b.width)
    assert len(sample) == 2
    for t in sample:
        rows, was = refcheck.program_rows(b, t), parent_program_rows(b, t)
        assert [j for j, _ in rows] == [j for j, _ in was] == [11, 12, 13, 14, 15]
        assert all(bool(jnp.array_equal(g, w)) for (_, g), (_, w) in zip(rows, was))


#: the live streams' contexts: a one-chip window of the ledger (27 of 32
#: slots, block_roofline 3.83 of a 947.5 ms block is 7.43 GB a step: 4,700
#: tokens of context), one like it with every context different, a full
#: batch, an idle one
LEDGER_SIZES = [[175] * 26 + [181], [33 + 11 * i for i in range(26)],
                [300] * 32, []]


@pytest.mark.parametrize("file, chips, per, row", [
    # int8 codes: 1 byte a parameter, 128 + 4 bytes a cached vector
    ("servebench/configs/mistral-7b-v0.3.json", 1, 1.0, 2 * 8 * 132.0),
    # bfloat16: 2 bytes a parameter, 256 bytes a cached vector
    ("servebench/configs/mistral-7b-v0.3-bf16-tp4.json", 4, 2.0, 2 * 8 * 256.0)])
def test_least_time_of_the_mistral_files_is_the_parents(file, chips, per, row):
    """Every field of `block_least_seconds`, equal (==, not approx) to
    the parent's formula (PR 26) written out over the dense count: the
    bytes and both times to the last bit whatever the contexts are (a
    dense file reads every row of every layer), and the operations with
    the attention's own products beside them (PR 43: 4 x 32 heads x 128
    a row read)."""
    assert file in MISTRAL
    config = json.loads((ROOT / file).read_text())
    params = 32 * (4096 * 32 * 128 * 2 + 4096 * 8 * 128 * 2 + 3 * 4096 * 14336) \
        + 32768 * 4096
    assert params == 7_113_539_584
    steps = config["serve"]["decode_steps_per_tick"]
    for contexts in LEDGER_SIZES:
        live, tokens = len(contexts), sum(contexts)
        by = steps * (params * per + tokens * 32 * row)
        fl = steps * (2.0 * params * max(1.0, live)
                      + 32 * tokens * 4.0 * 32 * 128)
        t_mem, t_cmp = by / (chips * 819e9), fl / (chips * 197e12)
        assert peaks.block_least_seconds(
            config, "TPU v5 lite", chips, steps, contexts) == {
                "bytes": by, "flops": fl, "memory_s": t_mem, "compute_s": t_cmp,
                "least_s": max(t_mem, t_cmp),
                "bound": "memory" if t_mem >= t_cmp else "compute",
                "parts": {"weights": params * per, "rows": tokens * 32 * row,
                          "index_keys": 0.0, "state": 0.0}}
