"""A third configuration enters BENCHMARK.json ITSELF by added files and
appended entries alone, and the benchmark's own tests still pass.

`toy.py` lays its cells over a COPY of the manifest inside the tests;
until PR 32 no test put a third entry into the manifest that the test
modules enumerate, and two of them could not have taken one: a cut
depth, a "model" group or a width above 1 failed by construction. Here
a temporary checkout gets what a `model_config` PR brings and nothing
else: a configuration at toy sizes with `reduced` not empty, a "model"
group, `decode_width` 4 and the published keys of a mixture of experts
whose expert width is not its `intermediate_size`; its pin, its
reference, a traffic file, a reader, and three entries appended to
BENCHMARK.json. The modules that enumerate the manifest then run INSIDE
that checkout, as a child, and the new cell runs once through
servebench/run.py.
"""
import filecmp
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
FILES = Path(__file__).resolve().parent / "files"
sys.path.insert(0, str(Path(__file__).resolve().parent))

from toy import CONFIGS as TOYS  # noqa: E402

MODULES = ["tests/servebench/test_servebench_manifest.py",
           "tests/servebench/test_servebench_refcheck.py",
           "tests/servebench/test_servebench_traffic.py"]
NAME, CELL = "third-moe", "third.batch"
SOURCE = "tests only: what a model_config PR brings, at toy sizes"


def third_files() -> dict:
    """{path relative to the checkout: text} of what the PR adds."""
    config = json.loads((FILES / "configs" / "tiny-moe.json").read_text())
    # cut from a source of 6 layers to 2; experts of width 96 beside a
    # dense width of 192 that no layer of the toy uses; the program has
    # no expert width of its own, so the group gives it as the program's
    # `intermediate_size`; a step of 4 positions a stream
    config.update(
        name=NAME, source=SOURCE, reduced=["num_hidden_layers"],
        published={"num_hidden_layers": 6}, intermediate_size=192,
        moe_intermediate_size=96, decode_width=4, reference="third_moe_f32",
        model={"arch": "mixtral", "moe_impl": "dense", "intermediate_size": 96})
    pin = {
        "name": NAME, "source": SOURCE,
        "published": {"hidden_size": 64, "num_hidden_layers": 6,
                      "num_attention_heads": 4, "num_key_value_heads": 2,
                      "head_dim": 16, "intermediate_size": 192,
                      "moe_intermediate_size": 96, "num_experts": 4,
                      "num_experts_per_tok": 2, "vocab_size": 512,
                      "rms_norm_eps": 1e-5, "rope_theta": 1e4},
        "held": {"num_hidden_layers": 2}, "model_group": True,
        "fields": {"vocab_size": 512, "hidden_size": 64, "num_layers": 2,
                   "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                   "intermediate_size": 96, "max_seq_len": 256,
                   "norm_eps": 1e-5, "rope_theta": 1e4,
                   "tie_embeddings": False, "act": "silu", "num_experts": 4,
                   "num_experts_per_tok": 2, "dtype": "float32",
                   "param_dtype": "float32", "arch": "mixtral",
                   "moe_impl": "dense"},
        "slots": 4, "max_seq": 128}
    traffic = json.loads((FILES / "traffic" / "tinybatch.json").read_text())
    traffic.update(name="thirdbatch", output={"dist": "uniform", "lo": 40, "hi": 80})
    return {
        f"servebench/configs/{NAME}.json": json.dumps(config, indent=2),
        f"servebench/pins/{NAME}.json": json.dumps(pin, indent=2),
        "servebench/references/third_moe_f32.py":
            (FILES / "references" / "toymoe_f32.py").read_text(),
        "servebench/traffic/thirdbatch.json": json.dumps(traffic, indent=2),
        "servebench/layer_metrics/third_finished.py":
            (FILES / "layer_metrics" / "tiny_finished.py").read_text()}


def appended(manifest: dict) -> dict:
    """The manifest with the PR's three entries at the end of its lists."""
    m = json.loads(json.dumps(manifest))
    m["configs"].append({
        "name": NAME, "source": SOURCE, "file": f"servebench/configs/{NAME}.json",
        "reduced": ["num_hidden_layers"],
        "why": "experts of their own width, a cut depth, a step of 4 positions"})
    m["workloads"].append({
        "name": CELL, "config": NAME, "traffic": "thirdbatch", "chips": 1,
        "why": "burst on a toy of what the room of PRs 26 and 27 is for"})
    m["per_layer"].append({
        "name": "third_finished", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "scheduler (sched/scheduler.py)",
        "moves": "out_tok_s", "workloads": [CELL]})
    return m


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("third")
    for d in ("servebench", "tests/servebench"):
        shutil.copytree(ROOT / d, root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    shutil.copy(ROOT / "PERF.md", root / "PERF.md")     # the list of layers
    for rel, text in third_files().items():
        assert not (root / rel).exists(), rel
        (root / rel).write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(
        appended(json.loads((ROOT / "BENCHMARK.json").read_text())), indent=1))
    return root


def child(root, *argv, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS" and not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    return subprocess.run([sys.executable, *argv], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def pytest_child(root, *argv):
    return child(root, "-m", "pytest", "-v", "-p", "no:cacheprovider",
                 "--rootdir", str(root), "-c", os.devnull, *argv)


def test_the_modules_that_enumerate_the_manifest_pass_in_the_checkout(checkout):
    r = pytest_child(checkout, *MODULES)
    assert r.returncode == 0, r.stdout[-6000:] + r.stderr[-2000:]
    # and they did enumerate the third entry
    for case in (f"test_configuration_builds_the_published_model[{NAME}]",
                 f"test_cell_resolves_to_files[{CELL}]",
                 f"test_every_configuration_of_the_manifest_can_be_checked[{NAME}]"):
        assert re.search(re.escape(case) + r" PASSED", r.stdout), case
    # with the two Mistral files still held to the parent's check, by name
    assert len(re.findall(r"test_mistral_files_are_sampled_as_the_parent_did"
                          r"\[[^\]]+\] PASSED", r.stdout)) == 2


@pytest.mark.parametrize("trace", [0, 1])
def test_the_third_cell_runs_and_is_correct(checkout, trace):
    r = child(checkout, "servebench/run.py", "--workload", CELL, "--seed",
              str(2 ** 31 + 3232 + trace), "--seconds", "4", "--trace",
              str(trace), "--rehearsal", timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = (json.loads(ln) for ln in
                 [ln for ln in r.stdout.splitlines() if ln.strip()][-2:])
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    assert out["device"]["platform"] == "cpu"
    if not trace:
        assert set(out["metrics"]) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
        return
    # the entry that only this cell can read, beside the benchmark's own
    assert {"third_finished", "tick_host_share", "slot_occupancy"} <= \
        set(out["metrics"])
    assert out["metrics"]["third_finished"]["value"] >= 4
    # its own reference, at its own width: every row of two calls of 4
    ref = info["refcheck"]
    assert ref["ok"] and ref["rms_err"] < 1e-4 == ref["tolerance"]
    assert (ref["reference"], ref["decode_width"], ref["positions"]) == \
        ("third_moe_f32", 4, 18)


def test_nothing_that_was_there_is_edited(checkout):
    """Files: the two trees differ by added files only. BENCHMARK.json:
    every entry that was there is equal and in place."""
    added = []

    def walk(d):
        assert not d.left_only and not d.diff_files and not d.funny_files, \
            (d.left, d.left_only, d.diff_files)
        added.extend(str(Path(d.right, n).relative_to(checkout))
                     for n in d.right_only)
        for sub in d.subdirs.values():
            walk(sub)
    for d in ("servebench", "tests/servebench"):
        walk(filecmp.dircmp(ROOT / d, checkout / d,
                            ignore=["__pycache__", ".pytest_cache"]))
    assert sorted(added) == sorted(third_files())
    was = json.loads((ROOT / "BENCHMARK.json").read_text())
    now = json.loads((checkout / "BENCHMARK.json").read_text())
    assert list(now) == list(was)
    for key, value in was.items():
        if key in ("configs", "workloads", "per_layer"):
            assert now[key][:len(value)] == value and len(now[key]) == len(value) + 1
        else:
            assert now[key] == value, key


def landed_before(manifest: dict, name: str) -> tuple:
    """A configuration that an earlier PR has landed: the manifest with
    its entry in FRONT of the third's, and the two files it brought
    (the third's under another name; no cell of its own is needed by
    what is run here)."""
    m = json.loads(json.dumps(manifest))
    at = [c["name"] for c in m["configs"]].index(NAME)
    m["configs"].insert(at, dict(m["configs"][at], name=name,
                                 file=f"servebench/configs/{name}.json"))
    files = {}
    for sub in ("configs", "pins"):
        body = json.loads(third_files()[f"servebench/{sub}/{NAME}.json"])
        files[f"servebench/{sub}/{name}.json"] = json.dumps(dict(body, name=name))
    return m, files


@pytest.mark.parametrize("landed", [0, 1], ids=["today", "after_a_model_config_pr"])
def test_without_its_pin_the_configuration_fails_by_name(checkout, landed):
    """And no other case does, HOWEVER MANY configurations the manifest
    holds by then: the count of cases comes from the checkout's own
    manifest and the toys, never from today's size of either. The second
    case is the checkout a later `model_config` PR leaves behind it."""
    manifest = checkout / "BENCHMARK.json"
    was = manifest.read_text()
    now, files = landed_before(json.loads(was), "before-" + NAME) if landed \
        else (json.loads(was), {})
    assert not [rel for rel in files if (checkout / rel).exists()]
    pin = checkout / "servebench" / "pins" / (NAME + ".json")
    away = pin.with_suffix(".away")
    pin.rename(away)
    try:
        for rel, text in files.items():
            (checkout / rel).write_text(text)
        manifest.write_text(json.dumps(now, indent=1))
        r = pytest_child(checkout, MODULES[0], "-k",
                         "test_configuration_builds_the_published_model")
    finally:
        away.rename(pin)
        manifest.write_text(was)
        for rel in files:
            (checkout / rel).unlink(missing_ok=True)
    assert r.returncode != 0
    assert f"configuration '{NAME}' of BENCHMARK.json has no pin" in r.stdout
    case = re.compile(r"test_configuration_builds_the_published_model"
                      r"\[([^\]]+)\] (PASSED|FAILED)")
    verdict = dict(case.findall(r.stdout))
    names = [c["name"] for c in now["configs"]] + list(TOYS)
    assert len(names) == len(json.loads(was)["configs"]) + landed + len(TOYS)
    assert verdict == {n: "FAILED" if n == NAME else "PASSED" for n in names}, \
        r.stdout[-3000:]
