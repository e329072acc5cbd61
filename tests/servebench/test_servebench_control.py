"""The control of the reference check (servebench/control.py) at a toy's
size on the CPU: the reference put in the program's place in the nearest
precision below the one the configuration states has to come out NOT
correct under the configuration's limit, and a sound program correct.
The chip's readings at the cells' own sizes are in PERF.md.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import control  # noqa: E402

FILES = "tests/servebench/files"


@pytest.mark.parametrize("name, is_stated, control_is", [
    ("tiny-moe", "float32", "bfloat16"),
    ("tiny-llama", "float32", "bfloat16"),
    # decode calls of 4 positions: nine rows a prompt, the same verdicts
    ("tiny-llama-w4", "float32", "bfloat16")])
def test_control_is_not_correct_and_the_program_is(name, is_stated, control_is):
    config = json.loads((ROOT / FILES / "configs" / (name + ".json")).read_text())
    # the toys state float32; their files' limit is for that (the llama
    # toy's file states none, and 0.12 is the chip's, for bfloat16)
    config.setdefault("reference_tolerance", 1e-4)
    r = control.measure(config, seeds=[2 ** 31 + 5, 7, 11], control_seeds=3,
                        paths=["servebench", FILES])
    assert (r["stated"], r["control_is"]) == (is_stated, control_is)
    assert len(r["sound"]) == len(r["control"][control_is]) == 3
    assert r["sound_max"] < 1e-5 < r["limit"] == 1e-4 < r["control_min"]
    assert r["ratio"] > 100 and r["holds"]


def test_the_ladder_and_the_rounding():
    import jax.numpy as jnp
    assert control.stated({"serve": {"quant": "int8"}}) == "int8"
    assert control.stated({"serve": {"quant": "none"}}) == "bfloat16"
    assert control.stated({"serve": {}, "torch_dtype": "float32"}) == "float32"
    assert control.LADDER["int8"][0] == "int4" and control.LADDER["bfloat16"][0] == "int8"
    x = jnp.asarray([[1.0, -0.5], [0.26, 0.124], [-0.9, 0.27]], jnp.float32)
    # one scale per output column: 1 / 7 and 0.5 / 7
    assert control.lowered(x, "int4") == pytest.approx(
        jnp.asarray([[7, -7], [2, 2], [-6, 4]]) * jnp.asarray([1 / 7, 0.5 / 7]))
    assert float(jnp.max(jnp.abs(control.lowered(x, "int8") - x))) < 0.5 / 127
    assert float(jnp.max(jnp.abs(control.lowered(x, "fp8") - x))) < 1 / 16
    assert control.lowered(x, "bfloat16").dtype == jnp.float32
    v = jnp.asarray([1.001, 2.003])
    assert control.lowered(v, "int4") is v          # a norm's scale stays
