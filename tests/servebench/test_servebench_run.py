"""The harness end to end on the CPU with a toy model (a rehearsal), and
the ways it must refuse to run.

The throw-away cell is added FROM FILES ALONE: a configuration, two
traffic mixes and a per-layer reader from tests/servebench/files/, plus
entries in a copy of BENCHMARK.json. No file of servebench/ is edited.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
FILES = Path(__file__).resolve().parent / "files"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, made by adding files and entries."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    for sub in ("configs", "traffic", "layer_metrics"):
        for p in (FILES / sub).iterdir():
            assert not (root / "servebench" / sub / p.name).exists()
            shutil.copy(p, root / "servebench" / sub / p.name)
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-llama", "source": "tests only",
                         "file": "servebench/configs/tiny-llama.json",
                         "reduced": [], "why": "a toy for the CPU"})
    m["workloads"] += [
        {"name": "tiny.batch", "config": "tiny-llama", "traffic": "tinybatch",
         "chips": 1, "why": "closed loop on a toy"},
        {"name": "tiny.chat", "config": "tiny-llama", "traffic": "tinychat",
         "chips": 1, "why": "open loop on a toy"}]
    # the open-loop cell brings its own end-to-end and per-layer metrics,
    # whose arithmetic and readers the benchmark already holds
    chat = ["tiny.chat"]
    m["end_to_end"] += [
        {"name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": chat},
        {"name": "gap_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": chat}]
    for e in m["end_to_end"]:
        if e["name"] == "out_tok_s":
            e["workloads"] = [w["name"] for w in m["workloads"]
                              if w["name"] != "tiny.chat"]
    for e in m["per_layer"]:
        if e["moves"] == "out_tok_s":
            e["moves"] = "tpot_p50_ms"
    m["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower", "source": source,
         "layer": "front end (serve/server.py)", "moves": "ttft_p50_ms",
         "workloads": chat}
        for name, source in (("gen_late_p99_ms", "host_clock"),
                             ("front_ms_p50", "program_span"),
                             ("queue_wait_p50_ms", "program_span"))]
    m["per_layer"].append(
        {"name": "tiny_finished", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "load generator (servebench/client.py)",
         "moves": "tpot_p50_ms", "workloads": ["tiny.batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def run(root, *argv, timeout=240):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    env["JAX_PLATFORMS"] = "cpu"      # the sandbox has no chip; never take one
    env["BENCH_RUN"] = "ignored"
    return subprocess.run([sys.executable, str(root / "servebench" / "run.py"),
                           *argv], cwd=root, env=env, capture_output=True,
                          text=True, timeout=timeout)


def last_json(r):
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert lines, r.stderr[-3000:]
    return [json.loads(ln) for ln in lines[-2:]]


def test_throwaway_cell_from_files_alone_runs(checkout):
    r = run(checkout, "--workload", "tiny.batch", "--seed", str(2 ** 31 + 99),
            "--seconds", "4", "--trace", "0", "--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = last_json(r)
    assert set(out) == KEYS
    assert out["correct"] is True, r.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] >= 4
    assert set(out["metrics"]) == {"out_tok_s", "tpot_p50_ms", "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert out["device"]["platform"] == "cpu"       # a rehearsal says so
    assert set(info["thirds"]) == {"out_tok_s", "tpot_p50_ms"}
    assert all(len(v) == 3 for v in info["thirds"].values())
    assert info["samples"]["tpot_p50_ms"] >= 4


def test_traced_rehearsal_prints_no_device_metric(checkout):
    r = run(checkout, "--workload", "tiny.chat", "--seed", "11",
            "--seconds", "4", "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = last_json(r)
    assert set(out) >= KEYS and "breakdown" not in out
    m = json.loads((checkout / "BENCHMARK.json").read_text())
    source = {e["name"]: e["source"] for e in m["per_layer"]}
    assert out["metrics"], r.stderr[-3000:]
    assert all(source[k] != "device_trace" for k in out["metrics"])
    assert {"gen_late_p99_ms", "queue_wait_p50_ms", "tick_host_share",
            "front_ms_p50", "prefill_tok_s"} <= set(out["metrics"])
    assert "busy_s" not in out["device"] and "window_s" not in out["device"]
    # the comparison with the plain reference ran, in float32 on the toy
    assert info["refcheck"]["ok"] and info["refcheck"]["max_err"] < 1e-4


def test_refuses_without_a_chip(checkout):
    r = run(checkout, "--workload", "tiny.batch", "--seed", "1",
            "--seconds", "2", "--trace", "0", timeout=120)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert "TPU" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(ROOT / "servebench", tmp_path / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = run(tmp_path, "--workload", "mistral7b.batch", "--seed", "1",
            "--seconds", "2", "--trace", "0", timeout=60)
    assert r.returncode != 0 and not r.stdout.strip()


def test_unknown_cell_is_an_error(checkout):
    r = run(checkout, "--workload", "no.such", "--seed", "1", "--seconds", "2",
            "--trace", "0", "--rehearsal", timeout=60)
    assert r.returncode != 0 and not r.stdout.strip()
