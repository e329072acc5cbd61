"""BENCHMARK.json against the files it names, the knee rule, the peaks."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import peaks  # noqa: E402
from servebench.knee import find_knee, sustained  # noqa: E402
from servebench.launcher import model_fields, serve_argv  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.metrics import END_TO_END  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert all(len(w["why"]) <= 200 for w in MANIFEST["workloads"])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files(name):
    cell = Cell(MANIFEST, name, ROOT)
    assert cell.config_path.exists() and cell.traffic_path.exists()
    assert cell.config["chips"] == cell.chips
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert e2e - {"setup_s"} <= set(END_TO_END)
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
        assert m["moves"] in e2e, (m["name"], m["moves"])
    plan = make_plan(load_traffic(cell.traffic_path), 2 ** 31 + 7,
                     MANIFEST["run_seconds"], cell.config["vocab_size"],
                     cell.config["serve"]["max_seq"])
    assert plan.queues or plan.schedule


def test_every_per_layer_metric_has_a_reader_and_a_layer():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md does not list the layer {layer!r}"
    for m in MANIFEST["per_layer"]:
        assert (ROOT / "servebench" / "layer_metrics" / (m["name"] + ".py")).exists()
        assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_builds_the_published_model(cfg):
    config = json.loads((ROOT / cfg["file"]).read_text())
    assert config["name"] == cfg["name"] and config["source"] == cfg["source"]
    assert cfg["reduced"] == config["reduced"] == []
    f = model_fields(config)
    # Mistral-7B-v0.3's published sizes
    assert (f["hidden_size"], f["num_layers"], f["num_heads"], f["num_kv_heads"],
            f["head_dim"], f["intermediate_size"], f["vocab_size"]) == \
        (4096, 32, 32, 8, 128, 14336, 32768)
    assert f["rope_theta"] == 1e6 and f["norm_eps"] == 1e-5 and f["arch"] == "llama"
    argv = serve_argv(config, 1234)
    assert argv[:3] == ["serve", "--model", cfg["name"]]
    assert "--max-batch" in argv and "--decode-steps-per-tick" in argv
    from butterfly_tpu.serve.cli import build_parser
    args = build_parser().parse_args(argv)
    assert args.max_batch == 32 and args.max_seq == 2048
    assert (ROOT / "servebench" / "references" / (config["reference"] + ".py")).exists()


def test_sliding_window_is_refused():
    config = json.loads((ROOT / MANIFEST["configs"][0]["file"]).read_text())
    with pytest.raises(ValueError):
        model_fields(dict(config, sliding_window=4096))


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")


def test_block_bytes_of_mistral_7b():
    one = json.loads((ROOT / "servebench/configs/mistral-7b-v0.3.json").read_text())
    four = json.loads((ROOT / "servebench/configs/mistral-7b-v0.3-bf16-tp4.json").read_text())
    assert peaks.matmul_params(one) == pytest.approx(7.11e9, rel=0.01)
    assert peaks.weight_bytes(four) == 2 * peaks.weight_bytes(one)
    assert peaks.kv_bytes_per_token(one) == 32 * 2 * 8 * 132
    assert peaks.kv_bytes_per_token(four) == 32 * 2 * 8 * 256
    r = peaks.block_least_seconds(one, "TPU v5 lite", 1, 4, 32, 32 * 300)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(4 * (7.11e9 + 9600 * 67584) / 819e9, rel=0.01)
    r4 = peaks.block_least_seconds(four, "TPU v5 lite", 4, 4, 32, 32 * 300)
    assert r4["least_s"] < r["least_s"]


def test_knee_rule():
    def pt(rate, tok, p95, a, b, q=0):
        return {"rate_rps": rate, "out_tok_s": tok, "ttft_p95_ms": p95,
                "ttft_p50_first_half_ms": a, "ttft_p50_second_half_ms": b,
                "queue_depth_close": q}
    pts = [pt(0.2, 15, 900, 500, 520), pt(0.4, 30, 1500, 700, 800),
           pt(0.6, 40, 5000, 1500, 2000), pt(0.8, 42, 30000, 4000, 20000, 9)]
    assert [sustained(p) for p in pts] == [True, True, True, False]
    knee = find_knee(pts)
    assert knee["rate_rps"] == 0.4          # 0.6 busts 2x the best tail
    assert find_knee(pts, slack=10)["rate_rps"] == 0.6
    assert find_knee([pts[3]]) is None
