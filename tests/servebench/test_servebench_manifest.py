"""BENCHMARK.json against the files it names, the knee rule, the peaks."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import peaks  # noqa: E402
from servebench.knee import find_knee, sustained  # noqa: E402
from servebench.launcher import model_fields, serve_argv  # noqa: E402
from servebench.manifest import Cell, find_under_paths, load_manifest  # noqa: E402
from servebench.metrics import END_TO_END  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

from toy import CELLS as TOY_CELLS, with_toy_cells  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
#: the tests' throw-away cells, resolved where their files lie: a path
#: of the benchmark's own holds them, as a later PR's directory would
FILES = "tests/servebench/files"
TOY = with_toy_cells(dict(MANIFEST, paths=MANIFEST["paths"] + [FILES]), FILES)
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


@pytest.mark.parametrize("name", CELLS + [c[0] for c in TOY_CELLS])
def test_cell_resolves_to_files(name):
    cell = Cell(MANIFEST if name in CELLS else TOY, name, ROOT)
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


#: keys of the source a pin must state under `published`, whatever
#: else it pins: the sizes, `rope_theta` and the norm's epsilon
PIN_PUBLISHED = {"hidden_size", "num_hidden_layers", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "intermediate_size",
                 "vocab_size", "rms_norm_eps", "rope_theta"}


def pin_of(manifest, name):
    """`<path>/pins/<name>.json` under one of the manifest's `paths`:
    what the configuration is held to, written by hand from the source
    by the PR that brings the configuration (PERF.md, section 7, says
    what each key holds). A configuration without one fails, by name."""
    try:
        path = find_under_paths(manifest, ROOT, "pins", name + ".json")
    except FileNotFoundError as e:
        pytest.fail(f"configuration {name!r} of BENCHMARK.json has no pin "
                    f"pins/{name}.json: {e}")
    return json.loads(path.read_text())


def held_to_its_pin(cfg, manifest):
    """One entry of `configs` against its file and its pin."""
    config = json.loads((ROOT / cfg["file"]).read_text())
    pin = pin_of(manifest, cfg["name"])
    assert config["name"] == cfg["name"] == pin["name"]
    assert config["source"] == pin["source"]
    if cfg in MANIFEST["configs"]:
        assert config["source"] == cfg["source"]
    # `reduced` is compared, not forbidden: the entry's list is the
    # file's, the pin holds a value for each of its keys and for no
    # other, the file states the published value beside the one it holds
    # and the two differ; every other key is the source's
    published, held = pin["published"], pin["held"]
    assert PIN_PUBLISHED <= set(published), cfg["name"]
    assert cfg["reduced"] == config["reduced"], \
        f"{cfg['name']}: the entry's `reduced` is not the file's"
    assert len(set(config["reduced"])) == len(config["reduced"])
    assert set(config["reduced"]) == set(held) == set(config.get("published", {})), \
        f"{cfg['name']}: `reduced`, the pin's `held` and the file's " \
        "`published` group do not name the same keys"
    for key, value in published.items():
        if key in held:
            assert config["published"][key] == value, (cfg["name"], key)
            assert config[key] == held[key] != value, \
                f"{cfg['name']}: {key!r} is listed in `reduced` and not cut"
        else:
            assert config[key] == value, \
                f"{cfg['name']}: {key!r} differs from the source and is not in `reduced`"
    # the program's ModelConfig is built with the pin's `fields`, the
    # whole dict and no more: the family, the epsilon, the types too
    f = model_fields(config)
    assert f == pin["fields"], cfg["name"]
    assert ("model" in config) == pin["model_group"]
    experts = "num_experts" in pin["fields"]
    assert experts == ("num_experts_per_tok" in pin["fields"]) == ("num_experts" in f)
    from butterfly_tpu.core.config import ModelConfig
    assert ModelConfig(**f).is_moe == experts
    argv = serve_argv(config, 1234)
    assert argv[:3] == ["serve", "--model", cfg["name"]]
    assert "--max-batch" in argv and "--decode-steps-per-tick" in argv
    from butterfly_tpu.serve.cli import build_parser
    args = build_parser().parse_args(argv)
    assert (args.max_batch, args.max_seq) == (pin["slots"], pin["max_seq"])
    assert args.max_queue == config["serve"].get("max_queue", 256)
    assert any((ROOT / d / "references" / (config["reference"] + ".py")).exists()
               for d in manifest["paths"])


@pytest.mark.parametrize("cfg", TOY["configs"], ids=lambda c: c["name"])
def test_configuration_builds_the_published_model(cfg):
    held_to_its_pin(cfg, TOY)


def pin_files():
    """Every pin under the manifest's paths and the toys', as paths
    relative to the checkout."""
    return sorted(str(p.relative_to(ROOT)) for d in TOY["paths"]
                  for p in (ROOT / d / "pins").glob("*.json"))


@pytest.mark.parametrize("pin", pin_files())
def test_every_pin_is_some_configurations(pin):
    """A pin lies beside the configuration it holds, under the same
    path and the same name, and that configuration is one of the
    manifest's or a toy: a pin of nothing would be held to nothing."""
    path = ROOT / pin
    name = json.loads(path.read_text())["name"]
    assert name == path.stem
    file = path.parent.parent / "configs" / path.name
    assert [c["name"] for c in TOY["configs"]
            if ROOT / c["file"] == file] == [name]


def cut_file(tmp_path, config, pin):
    """A throw-away configuration and its pin under a path of their
    own: the manifest entry that names them, and the manifest."""
    files = tmp_path / "cut"
    for sub, body in (("configs", config), ("pins", pin)):
        (files / sub).mkdir(parents=True, exist_ok=True)
        (files / sub / (config["name"] + ".json")).write_text(json.dumps(body))
    entry = {"name": config["name"], "source": "tests only",
             "reduced": list(config["reduced"]),
             "file": str(files / "configs" / (config["name"] + ".json"))}
    return entry, dict(TOY, paths=TOY["paths"] + [str(files)])


def cut_toy():
    """`tiny-moe` cut from a source of 6 layers to the 2 it has, with
    its pin."""
    config = json.loads((ROOT / FILES / "configs" / "tiny-moe.json").read_text())
    pin = json.loads((ROOT / FILES / "pins" / "tiny-moe.json").read_text())
    config.update(name="tiny-cut", reduced=["num_hidden_layers"],
                  published={"num_hidden_layers": 6})
    pin.update(name="tiny-cut", held={"num_hidden_layers": 2},
               published=dict(pin["published"], num_hidden_layers=6))
    return config, pin


def test_a_cut_depth_passes_when_file_pin_and_entry_agree(tmp_path):
    held_to_its_pin(*cut_file(tmp_path, *cut_toy()))


def not_cut(config, pin, entry):
    config["num_hidden_layers"] = 6


def not_listed(config, pin, entry):
    config.update(reduced=[], published={})
    pin["held"] = {}


def no_published_value(config, pin, entry):
    del config["published"]


def entry_lists_nothing(config, pin, entry):
    entry["reduced"] = []


def another_width(config, pin, entry):
    config["hidden_size"] = 32


@pytest.mark.parametrize("fault, says", [
    (not_cut, "'num_hidden_layers' is listed in `reduced` and not cut"),
    (not_listed, "'num_hidden_layers' differs from the source and is not in `reduced`"),
    (no_published_value, "`reduced`, the pin's `held` and the file's `published` group do"),
    (entry_lists_nothing, "the entry's `reduced` is not the file's"),
    (another_width, "'hidden_size' differs from the source"),
], ids=lambda v: v.__name__ if callable(v) else "")
def test_a_cut_that_the_lists_do_not_bear_out_fails(tmp_path, fault, says):
    config, pin = cut_toy()
    over = {}
    fault(config, pin, over)
    entry, manifest = cut_file(tmp_path, config, pin)
    with pytest.raises(AssertionError, match="tiny-cut: " + says):
        held_to_its_pin(dict(entry, **over), manifest)


def test_a_configuration_without_a_pin_fails_by_name(tmp_path):
    entry, manifest = cut_file(tmp_path, *cut_toy())
    (tmp_path / "cut" / "pins" / "tiny-cut.json").unlink()
    with pytest.raises(pytest.fail.Exception, match="'tiny-cut'.*has no pin"):
        held_to_its_pin(entry, manifest)


def moe_file(**model):
    config = json.loads((ROOT / FILES / "configs" / "tiny-moe.json").read_text())
    return dict(config, model=model)


def test_model_group_is_laid_over_the_published_keys():
    f = model_fields(moe_file(arch="mixtral", moe_impl="ep", head_dim=32))
    assert f["arch"] == "mixtral" and f["moe_impl"] == "ep"
    assert f["head_dim"] == 32 and f["hidden_size"] == 64
    # Mixtral's own key for the number of experts, and OLMoE's
    mixtral = {k: v for k, v in moe_file().items() if k != "num_experts"}
    assert model_fields(dict(mixtral, model_type="mixtral", model={},
                             num_local_experts=8))["num_experts"] == 8
    assert model_fields(moe_file(arch="mixtral"))["num_experts"] == 4


def test_unknown_model_field_is_named_before_anything_is_built():
    with pytest.raises(ValueError) as e:
        model_fields(moe_file(arch="mixtral", norm_topk_prob=False))
    assert "'norm_topk_prob'" in str(e.value)
    assert "num_experts_per_tok" in str(e.value)     # the fields there are


def test_unknown_family_without_an_arch_is_an_error():
    with pytest.raises(ValueError, match="toymoe"):
        model_fields(moe_file())


def test_sliding_window_is_refused():
    config = json.loads((ROOT / MANIFEST["configs"][0]["file"]).read_text())
    with pytest.raises(ValueError, match="sliding-window"):
        model_fields(dict(config, sliding_window=4096))
    # whatever a "model" group says: `decode_window` is a field of the
    # program's, and no sliding window
    with pytest.raises(ValueError, match="sliding-window"):
        model_fields(dict(config, sliding_window=4096,
                          model={"decode_window": 4096}))


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
    # a cached row of one layer: int8 codes and a float32 scale a vector, or bf16
    assert peaks.cached_row_bytes(one) == 2 * 8 * 132
    assert peaks.cached_row_bytes(four) == 2 * 8 * 256
    r = peaks.block_least_seconds(one, "TPU v5 lite", 1, 4, [300] * 32)
    assert r["bound"] == "memory"
    assert r["parts"]["rows"] == 9600 * 32 * 2 * 8 * 132 == 9600 * 67584
    assert r["least_s"] == pytest.approx(4 * (7.11e9 + 9600 * 67584) / 819e9, rel=0.01)
    r4 = peaks.block_least_seconds(four, "TPU v5 lite", 4, 4, [300] * 32)
    assert r4["least_s"] < r["least_s"]


#: published sizes (config.json of mistralai/Mixtral-8x7B-v0.1 and of
#: allenai/OLMoE-1B-7B-0125-Instruct), served in int8
MIXTRAL_8X7B = dict(hidden_size=4096, head_dim=128, num_attention_heads=32,
                    num_key_value_heads=8, num_hidden_layers=32,
                    intermediate_size=14336, vocab_size=32000,
                    num_local_experts=8, num_experts_per_tok=2,
                    serve={"quant": "int8", "kv_quant": "int8"})
OLMOE_1B_7B = dict(hidden_size=2048, head_dim=128, num_attention_heads=16,
                   num_key_value_heads=16, num_hidden_layers=16,
                   intermediate_size=1024, vocab_size=50304, num_experts=64,
                   num_experts_per_tok=8,
                   serve={"quant": "int8", "kv_quant": "int8"})


def by_hand(attn, expert, router, layers, head, experts_read):
    return layers * (attn + router + experts_read * expert) + head


@pytest.mark.parametrize("n", [1, 32, 1024])
def test_params_streamed_and_multiplied_of_two_moes(n):
    """Worked by hand. Mixtral-8x7B: attention 4096 x 128 x (32 + 8) x 2 =
    41,943,040 a layer, one expert 3 x 4096 x 14336 = 176,160,768, router
    4096 x 8, head 32000 x 4096. OLMoE-1B-7B: attention 2048 x 128 x (16 +
    16) x 2 = 16,777,216, one expert 3 x 2048 x 1024 = 6,291,456, router
    2048 x 64, head 50304 x 2048."""
    mix = (41_943_040, 176_160_768, 32_768, 32, 131_072_000)
    olm = (16_777_216, 6_291_456, 131_072, 16, 103_022_592)
    # a token is multiplied by its k experts, whatever else is live
    assert peaks.matmul_params(MIXTRAL_8X7B) == by_hand(*mix, 2) == 12_748_587_008
    assert peaks.matmul_params(OLMOE_1B_7B) == by_hand(*olm, 8) == 1_178_861_568
    # a step streams the experts its n streams touch: E (1 - (1 - k/E)^n)
    touched = {"mix": {1: 2.0, 32: 8 * (1 - 0.75 ** 32), 1024: 8.0},
               "olm": {1: 8.0, 32: 64 * (1 - 0.875 ** 32), 1024: 64.0}}
    assert touched["mix"][32] == pytest.approx(7.99919, abs=1e-5)
    assert touched["olm"][32] == pytest.approx(63.1079, abs=1e-3)
    assert peaks.streamed_params(MIXTRAL_8X7B, n) == pytest.approx(
        by_hand(*mix, touched["mix"][n]), rel=1e-12)
    assert peaks.streamed_params(OLMOE_1B_7B, n) == pytest.approx(
        by_hand(*olm, touched["olm"][n]), rel=1e-12)
    if n == 1024:      # every expert: the whole model but its embedding
        assert peaks.streamed_params(MIXTRAL_8X7B, n) == pytest.approx(46_571_454_464)
        assert peaks.streamed_params(OLMOE_1B_7B, n) == pytest.approx(6_816_006_144)
    # a block: bytes by what is streamed, operations by what multiplies
    r = peaks.block_least_seconds(OLMOE_1B_7B, "TPU v5 lite", 1, 4, [0] * n)
    assert r["bytes"] == pytest.approx(4 * peaks.streamed_params(OLMOE_1B_7B, n))
    assert r["flops"] == 4 * 2 * 1_178_861_568 * n
    assert r["bound"] == ("memory" if n < 1024 else "compute")


#: published sizes (config.json of JetLM/SDAR-30B-A3B-Chat) at 8 of its 48
#: layers, in bfloat16; generation by blocks of 4 positions
SDAR_30B_A3B = dict(hidden_size=2048, head_dim=128, num_attention_heads=32,
                    num_key_value_heads=4, num_hidden_layers=8,
                    intermediate_size=6144, moe_intermediate_size=768,
                    vocab_size=151936, num_experts=128, num_experts_per_tok=8,
                    decode_width=4, serve={"quant": "none", "kv_quant": "none"})


def test_a_step_of_four_positions_a_stream_with_experts():
    """Worked by hand. Attention 2048 x 128 x (32 + 4) x 2 = 18,874,368 a
    layer, router 2048 x 128 = 262,144, one expert 3 x 2048 x 768 =
    4,718,592 (`moe_intermediate_size`, not the 6144 of
    `intermediate_size`), head 151,936 x 2048 = 311,164,928. A position
    meets 8 experts: 18,874,368 + 262,144 + 8 x 4,718,592 = 56,885,248 a
    layer; 8 layers and the head 455,081,984 + 311,164,928 = 766,246,912
    (all 48: 2,730,491,904 + 311,164,928 = 3,041,656,832, the "A3B")."""
    sdar = (18_874_368, 4_718_592, 262_144, 8, 311_164_928)
    assert by_hand(*sdar, 8) == 8 * 56_885_248 + 311_164_928 == 766_246_912
    assert peaks.matmul_params(SDAR_30B_A3B) == 766_246_912
    assert peaks.matmul_params(dict(SDAR_30B_A3B, num_hidden_layers=48)) == \
        48 * 56_885_248 + 311_164_928 == 3_041_656_832
    # a step of 32 streams x 4 positions is 128 tokens' draws of 8 in 128:
    # 0.9375^128 = 2.587e-4 of the experts are missed, 0.9375^32 = 0.1268
    wide, narrow = 128 * (1 - 0.9375 ** 128), 128 * (1 - 0.9375 ** 32)
    assert wide == pytest.approx(127.967, abs=1e-3)
    assert narrow == pytest.approx(111.771, abs=1e-3)
    assert peaks.streamed_params(SDAR_30B_A3B, 32 * 4) == pytest.approx(
        by_hand(*sdar, wide), rel=1e-12)
    # the block: 4 steps, 32 streams, 32 x 300 tokens of live context at
    # 8 layers x 2 x 4 KV heads x 128 x 2 bytes = 16,384 bytes a token,
    # which a step reads ONCE whatever its width; each of a stream's 4
    # positions multiplies the rows it reads: 4 x 32 heads x 128 a row
    assert 8 * peaks.cached_row_bytes(SDAR_30B_A3B) == 16_384
    assert peaks.row_flops(SDAR_30B_A3B) == 16_384
    r = peaks.block_least_seconds(SDAR_30B_A3B, "TPU v5 lite", 1, 4, [300] * 32)
    assert 4 * 2 * 766_246_912 * 32 * 4 == 784_636_837_888
    assert r["flops"] == 784_636_837_888 + 4 * 4 * 8 * 9600 * 16_384 \
        == 804_769_497_088
    assert r["bytes"] == pytest.approx(
        4 * (2 * by_hand(*sdar, wide) + 9600 * 16_384), rel=1e-12)
    assert r["bound"] == "memory"       # 43.0 GB: 52.5 ms against 4.0 ms
    assert r["memory_s"] == pytest.approx(0.0525, rel=0.01)
    assert r["compute_s"] == pytest.approx(0.00409, rel=0.01)
    # the same file read at width 1, as the count stood before the key:
    # a quarter of the operations, and 111.77 / 127.97 of the experts' bytes
    r1 = peaks.block_least_seconds(dict(SDAR_30B_A3B, decode_width=1),
                                   "TPU v5 lite", 1, 4, [300] * 32)
    assert r1["flops"] * 4 == r["flops"]
    assert r1["bytes"] == pytest.approx(
        4 * (2 * by_hand(*sdar, narrow) + 9600 * 16_384), rel=1e-12)
    assert 1 - narrow / wide == pytest.approx(0.1266, abs=1e-4)
    # no live stream counts as one, of 4 positions
    assert peaks.block_least_seconds(SDAR_30B_A3B, "TPU v5 lite", 1, 4, [])[
        "flops"] == 4 * 2 * 766_246_912 * 4


def test_leading_dense_layers_and_shared_experts_count():
    """A DeepSeek-style layout at toy sizes: 1 dense layer of width 10,
    2 expert layers of 4 experts of width 3 with 2 per token and 1
    shared; hidden 8, heads 2 x 4, vocabulary 16."""
    c = dict(hidden_size=8, head_dim=4, num_attention_heads=2,
             num_key_value_heads=2, num_hidden_layers=3, intermediate_size=10,
             moe_intermediate_size=3, n_shared_experts=1,
             first_k_dense_replace=1, num_experts=4, num_experts_per_tok=2,
             vocab_size=16, serve={})
    attn, dense, expert, router, head = 8 * 4 * 4 * 2, 3 * 8 * 10, 3 * 8 * 3, 32, 128
    assert peaks.matmul_params(c) == \
        3 * attn + dense + 2 * (router + (2 + 1) * expert) + head
    assert peaks.streamed_params(c, 1) == peaks.matmul_params(c)
    assert peaks.streamed_params(c, 1000) == pytest.approx(
        3 * attn + dense + 2 * (router + (4 + 1) * expert) + head)
    assert peaks.weight_bytes(c, 1000) == 2 * peaks.streamed_params(c, 1000)


def test_dense_counts_do_not_depend_on_the_live_streams():
    one = json.loads((ROOT / "servebench/configs/mistral-7b-v0.3.json").read_text())
    assert peaks.matmul_params(one) == 7_113_539_584
    for n in (1, 32, 1024):
        assert peaks.streamed_params(one, n) == 7_113_539_584
        assert peaks.weight_bytes(one, n) == 7_113_539_584.0


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
