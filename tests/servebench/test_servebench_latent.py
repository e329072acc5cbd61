"""What PR 44 adds to the benchmark, on records written out by hand: the
least time of a latent-attention model's read of its cached rows at the
cell's sizes (`servebench/latent_peaks.py`, a sum of
`servebench/peaks.py`'s own counts), its three readers, the traffic file
`longthink.json`, the configuration file and the entries in the
manifest; and a toy of the family through the harness on the CPU (a
rehearsal), added from files alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import latent_peaks, peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELL = Cell(MANIFEST, "joyai48b.longthink", ROOT)
CONFIG = CELL.config
V5E = "TPU v5 lite"
FILES = Path(__file__).resolve().parent / "files"


# -- the least time, worked by hand at the cell's sizes ----------------------

def test_sizes_of_one_row_and_of_the_absorbed_expansions():
    assert latent_peaks.is_latent(CONFIG)
    assert peaks.cached_row_bytes(CONFIG) == (512 + 64) * 2 == 1152
    assert latent_peaks.absorbed_params(CONFIG) == 512 * 32 * (128 + 128) \
        == 4_194_304
    # a row read costs 32 heads their scores over 576 and a sum over 512
    assert peaks.row_flops(CONFIG) == 2 * 32 * (576 + 512) == 69_632
    assert not latent_peaks.is_latent(
        Cell(MANIFEST, "keye30b.think", ROOT).config)


@pytest.mark.parametrize("context", [500, 3100, 8000])
def test_the_rows_are_peaks_py_s_rows_to_the_byte(context):
    """The part cannot disagree with the whole: the read's rows are
    `step_parts`' `rows`, 96 streams x 6 layers x 1,152 B a token."""
    contexts = [context] * 96
    got = latent_peaks.latent_least_seconds(CONFIG, V5E, 1, 1, contexts)
    whole, _ = peaks.step_parts(CONFIG, contexts)
    assert got["parts"]["rows"] == whole["rows"] == 96 * 6 * context * 1152
    assert got["rows_read"] == peaks.total_rows_read(CONFIG, contexts) \
        == 96 * 6 * context
    assert got["parts"]["expansions"] == 6 * 4_194_304      # int8: a byte
    assert got["bytes"] == whole["rows"] + 6 * 4_194_304
    assert got["flops"] == 96 * 6 * context * 69_632 \
        + 2.0 * 96 * 6 * 4_194_304
    # 60 operations a byte against the chip's 240: memory-bound
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(got["bytes"] / 819e9)
    # a block of four steps is four times one
    four = latent_peaks.latent_least_seconds(CONFIG, V5E, 1, 4, contexts)
    assert four["least_s"] == pytest.approx(4 * got["least_s"])


def test_the_read_s_share_of_a_step_by_bytes():
    """ISSUE 44's arithmetic: at 96 streams of 3,100 a step moves 6.25 GB
    of weights and 2.06 GB of rows, least 10.1 ms; at 4,180, 2.77 GB and
    11.0 ms."""
    for context, rows, least in ((3100, 2.057e9, 10.14e-3),
                                 (4180, 2.774e9, 11.01e-3)):
        got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, [context] * 96)
        assert got["parts"]["rows"] == pytest.approx(rows, rel=1e-3)
        assert got["parts"]["weights"] == pytest.approx(6.246e9, rel=1e-3)
        assert got["least_s"] == pytest.approx(least, rel=1e-3)
        assert got["bound"] == "memory"


# -- the read's operation in a trace ------------------------------------------

#: the Mosaic call as a traced run of the cell named it (my chip run,
#: PR 44, seed 2147488044): the mixed block's and the decode block's
LATENT_OPS = [
    "_latent_attention.25___bf16_96_32_512__2_1_0:T_8_128__2_1_S_1___",
    "_latent_attention.24___bf16_96_32_512__2_1_0:T_8_128__2_1___cust",
    "_latent_attention___bf16_96_32_512__2_1_0:T_8_128__2_1_",
]
#: the same run's other operations: the experts' three products, the
#: head, the window's copies between the two layer runs, a chunk's
#: gathered pages, its scores and its weighted sum of latents ([.., 32,
#: 512] too, and no call), the absorbed queries, the flush's page; the
#: GQA kernel and the Mamba-2 step of the other cells; a call of this
#: name with another result, and another name that ends in this one
OTHER_OPS = [
    "_fusion.740___bf16_256_128_768__2_1_0:T_8_128__2_1_S_1___fusion_",
    "_fusion.739___bf16_256_128_1_768__3_1_0_2:T_8_128__2_1_S_1___fus",
    "_fusion.744___bf16_128_2048__1_0:T_8_128__2_1_S_1___fusion_bf16_",
    "_convolution_multiply_fusion.4___bf16_96_129280__1_0:T_8_128__2_",
    "_pad_maximum_fusion.3___bf16_6_96_1_256_640__4_3_1_2_0:T_8_128__",
    "_slice.734___bf16_5_96_1_256_640__4_3_1_2_0:T_8_128__2_1___slice",
    "_fusion.716___bf16_512_16_640__2_1_0:T_8_128__2_1_S_1___fusion_b",
    "_fusion.728____f32_32_32__0_1:T_8_128_S_1____f32_1_32_32_8192__3",
    "_fusion.730___bf16_32_1_32_512__3_2_0_1:T_8_128__2_1_S_1___fusio",
    "_fusion.706___bf16_128_1_32_192__0_3_2_1:T_8_128__2_1_S_1___fusi",
    "_while.3____s32___:T_128____bf16_6_49153_1_16_640__4_3_2_1_0:T_8",
    "_paged_attention.12___bf16_96_32_128__2_1_0:T_8_128__2_1_S_1___",
    "_ssm_step.8____f32_128_128_64__2_1_0",
    "_latent_attention.12___bf16_96_32_128__2_1_0",
    "_flatent_attention.12___bf16_96_32_512__2_1_0",
]


@pytest.mark.parametrize("name", LATENT_OPS)
def test_the_read_is_told_by_its_name_and_its_result(name):
    assert latent_peaks.latent_patterns(CONFIG).search(name)


@pytest.mark.parametrize("name", OTHER_OPS)
def test_another_operation_is_left_out(name):
    assert not latent_peaks.latent_patterns(CONFIG).search(name)


def test_the_gqa_kernel_s_share_reads_nothing_of_the_latent_call():
    """`paged_attn_share` keeps its meaning, the GQA Mosaic call: the
    latent call's name does not hold its pattern, so in this cell it
    reports 0 and not the latent read."""
    read = CELL.reader("paged_attn_share")
    ctx = SimpleNamespace(trace={"busy_s": 2.0, "ops": [
        [LATENT_OPS[0], 1.0, 100], [OTHER_OPS[2], 0.5, 100]]})
    assert read(ctx) == 0.0
    ctx.trace["ops"].append([OTHER_OPS[11], 0.5, 10])
    assert read(ctx) == pytest.approx(25.0)


# -- the three readers --------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


def traced_ctx():
    """A capture of 2.0 s: seven runs of the mixed block (the first cut
    by the capture's start, the last ending with it) and 0.8 s in the
    latent call."""
    ops = [[LATENT_OPS[0], 0.5, 600], [LATENT_OPS[1], 0.3, 200],
           [OTHER_OPS[0], 0.5, 100], [OTHER_OPS[8], 0.3, 10]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_mixed_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    streams = [stream(1000, 0.0, 30), stream(2000, 0.0, 30),
               stream(640, 0.0, 300), stream(1250, 5.0, 10),
               stream(900, 0.0, 5, end=0.6)]
    return SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                           device={"kind": V5E}, streams=streams,
                           trace_at=2.95, info={})


def test_latent_attn_share_on_a_trace_written_by_hand():
    assert CELL.reader("latent_attn_share")(traced_ctx()) == \
        pytest.approx(100 * 0.8 / 1.6)


def test_latent_attn_roofline_on_a_trace_written_by_hand():
    """Three streams generate at the trace's middle, holding 1,030,
    2,030 and 670 tokens; the read took 0.8 of the 1.9 s of block runs,
    so 0.3 x 0.8 / 1.9 of a whole block of four steps."""
    ctx = traced_ctx()
    least = latent_peaks.latent_least_seconds(
        CONFIG, V5E, 1, 4, [1030, 2030, 670])
    assert least["parts"]["rows"] == 6 * 3730 * 1152
    got = CELL.reader("latent_attn_roofline")(ctx)
    assert got == pytest.approx(100 * least["least_s"] / (0.3 * 0.8 / 1.9))
    assert 0 < got < 100
    assert ctx.info["latent_attn_roofline"]["streams"] == 3


@pytest.mark.parametrize("metric", ["latent_attn_share",
                                    "latent_attn_roofline"])
def test_nothing_to_read_is_none_and_never_raises(metric):
    read = CELL.reader(metric)
    ctx = traced_ctx()
    assert read(SimpleNamespace(**{**vars(ctx), "trace": {}})) is None
    assert read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    # a program that served the read without the call (the parent of a
    # later PR, kernels off): no operation matches
    bare = dict(ctx.trace, ops=[o for o in ctx.trace["ops"]
                                if "latent" not in o[0]])
    assert read(SimpleNamespace(**{**vars(ctx), "trace": bare})) is None
    # a configuration without latent attention (another cell's)
    other = {k: v for k, v in CONFIG.items() if k != "kv_lora_rank"}
    assert read(SimpleNamespace(**{**vars(ctx), "config": other})) is None


def tick(seq, rows, steps, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "latent_rows": rows,
            "latent_steps": steps}


def test_latent_rows_per_step_on_tick_records_written_by_hand():
    read = CELL.reader("latent_rows_per_step")
    streams = [stream(1000, 90.0, 300), stream(2000, 95.0, 300)]
    ctx = SimpleNamespace(
        w0=50.0, w1=150.0, wall_minus_mono=0.0, config=CONFIG, info={},
        streams=streams, ticks=[
            tick(1, 4 * 6 * 3100.0, 4),
            tick(2, 4 * 6 * 3200.0, 4), tick(2, 4 * 6 * 3200.0, 4),  # polled twice
            tick(3, 8 * 6 * 3300.0, 8),           # a tick that drained two
            tick(4, None, None),                  # a tick that drained none
            tick(5, 9e9, 4, t_wall=10.0)])        # before the window
    assert read(ctx) == pytest.approx(6 * (3100 + 3200 + 2 * 3300) / 4)
    # the benchmark's own count beside it: the clients' contexts at the
    # window's middle (t = 100: 101 and 51 tokens in) times the layers
    assert ctx.info["latent_rows_per_step"]["expected"] == \
        6 * (1000 + 101 + 2000 + 51)
    ctx.ticks = [tick(1, None, None), {"seq": 2, "t_wall": 100.0}]
    assert read(ctx) is None                  # the parent's records


def test_the_readers_on_tick_records_of_the_cell_s_shape():
    """Six records of a traced run of this cell (my chip run, PR 44,
    seed 2147488044), as `/debug/ticks` gave them: five mixed blocks and
    a decode block of four steps, 95 streams decoding at a mean context
    of 3,100. A block's four steps read 7.07 M rows over the six layers,
    and each block 9,120 more than the last: 95 streams stand 4
    positions further, in 4 steps and 6 layers."""
    ticks = json.loads((FILES.parent / "recorded_ticks"
                        / "joyai48b.longthink.json").read_text())
    assert len(ticks) == 6
    ctx = SimpleNamespace(config=CONFIG, ticks=ticks, wall_minus_mono=0.0,
                          streams=[], info={},
                          w0=ticks[0]["t_wall"] - 1, w1=ticks[-1]["t_wall"] + 1)
    rows = CELL.reader("latent_rows_per_step")(ctx)
    blocks = [t for t in ticks if t["latent_rows"] is not None]
    assert blocks and rows == pytest.approx(
        sum(t["latent_rows"] for t in blocks)
        / sum(t["latent_steps"] for t in blocks))
    # 96 slots of up to 8,192 positions in six layers
    assert 6 * 96 <= rows <= 6 * 96 * 8192
    assert all(t["latent_steps"] % 4 == 0 for t in blocks)
    assert [b["latent_rows"] - a["latent_rows"]
            for a, b in zip(blocks, blocks[1:])] == [95 * 4 * 4 * 6] * 5
    assert rows / (6 * 95) == pytest.approx(3110, abs=15)   # mean context
    assert all(t["ssm_rows"] is None and t["kv_rows_live"] is None
               for t in ticks)
    # the expert counters are in the records too (the two metrics'
    # `workloads` lists owe this cell: PERF.md section 7)
    assert all(0 < t["experts_touched"] <= 256 for t in blocks)
    assert peaks.num_experts(CONFIG) == 256


# -- the traffic file ---------------------------------------------------------

def plan_of(seed):
    return make_plan(load_traffic(CELL.traffic_path), seed, 45.0,
                     CONFIG["vocab_size"], CONFIG["serve"]["max_seq"])


def test_longthink_is_one_multiset_under_three_seeds():
    plans = [plan_of(s) for s in (1, 2 ** 31 + 7, 2 ** 31 + 2 ** 20)]
    shapes = [[[(len(r.tokens), r.max_tokens) for r in q] for q in p.queues]
              for p in plans]
    assert shapes[0] == shapes[1] == shapes[2]
    assert plans[0].queues[0][1].tokens != plans[1].queues[0][1].tokens
    p = plans[0]
    assert p.kind == "closed" and len(p.queues) == 96
    assert all(len(q) == 1 + 5 for q in p.queues)
    assert (p.lead_finished, p.lead_max_s) == (32, 240.0)


def test_longthink_s_first_wave_is_staggered_and_every_request_fits():
    p = plan_of(2 ** 31 + 99)
    firsts = sorted(q[0].max_tokens for q in p.queues)
    assert firsts == [64 * (i + 1) for i in range(96)]
    prompts = [len(r.tokens) for q in p.queues for r in q[1:]]
    assert min(prompts) >= 512 and max(prompts) <= 2048
    assert sum(prompts) / len(prompts) == pytest.approx(1108, abs=3)
    assert all(r.max_tokens == 6144 for q in p.queues for r in q[1:])
    assert max(len(r.tokens) + r.max_tokens
               for q in p.queues for r in q) <= 8192
    # 17.3 prompt tokens a step ride the decode rows: under the 32 a
    # step admits
    assert 96 * 1108 / 6144 == pytest.approx(17.3, abs=0.05)


# -- the manifest's entries and the configuration file ------------------------

def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, unit, source, layer, better, moves in (
            ("latent_attn_share", "%", "device_trace", "kernels (ops/)",
             "lower", "tpot_p50_ms"),
            ("latent_attn_roofline", "%", "device_trace", "kernels (ops/)",
             "higher", "tpot_p50_ms"),
            ("latent_rows_per_step", "rows", "program_counter",
             "cache manager (cache/)", "higher", "out_tok_s")):
        assert by[name] == {"name": name, "unit": unit, "better": better,
                            "source": source, "layer": layer, "moves": moves,
                            "workloads": ["joyai48b.longthink"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-3:] == [
        "latent_attn_share", "latent_attn_roofline", "latent_rows_per_step"]
    cfg = MANIFEST["configs"][-1]
    assert cfg["name"] == "joyai-llm-flash"
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == CONFIG["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    cell = MANIFEST["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        ("joyai48b.longthink", "joyai-llm-flash", "longthink", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    # every per-layer metric without a `workloads` list is the cell's too
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    assert unlisted <= {m["name"] for m in CELL.per_layer}
    assert {"block_roofline", "flush_ms_p50", "mixed_block_ms_p50",
            "kv_pages_used_peak", "paged_attn_share", "starved_share",
            "compiles_in_window"} <= unlisted
    assert {m["name"] for m in CELL.per_layer} - unlisted == {
        "latent_attn_share", "latent_attn_roofline", "latent_rows_per_step"}
    assert CONFIG["kernels_must_hold"] == ["latent_win"]
    assert CONFIG["dense_fallback_allowed"] is False
    assert {m["name"] for m in CELL.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}


def test_the_file_holds_every_published_key_and_its_bytes():
    cat = json.loads((ROOT / "servebench/pins/joyai-llm-flash.json")
                     .read_text())["published"]
    assert len(cat) == 36
    for key, value in cat.items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 6 >= 4
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["serve"] == {
        "quant": "int8", "kv_quant": "none", "max_batch": 96,
        "max_seq": 8192, "page_size": 16, "decode_steps_per_tick": 4}
    # the harness does not map n_routed_experts: the group gives it, and
    # names every field the parent's ModelConfig lacks
    assert CONFIG["model"]["num_experts"] == CONFIG["n_routed_experts"] == 256
    assert CONFIG["model"]["moe_intermediate_size"] == 768
    assert CONFIG["intermediate_size"] == 7168
    # ISSUE 44's arithmetic, a byte a parameter: attention, the dense
    # layer, an expert layer, the head; then the pool
    attn = peaks.attention_params(CONFIG)
    assert attn == 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 2048 == 26_345_472
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 768
    assert (dense, 256 * expert) == (44_040_192, 1_207_959_552)
    codes = 6 * attn + dense + 5 * (256 + 1) * expert + 129280 * 2048
    assert codes == 6_530_269_184
    weights = codes + 129280 * 2048 * 2 + 5 * 2048 * 256 * 2
    assert weights == pytest.approx(7.07e9, rel=2e-3)
    assert 96 * 8192 * 6 * 1152 == 5_435_817_984        # 5.44 GB of values
    assert 96 * 8192 // 16 == 49_152                    # pages
    assert set(CONFIG["assumed"]) >= {
        "num_nextn_predict_layers", "rope_interleave", "expert_split",
        "torch_dtype", "pool_lanes", "head_dim"}
    assert "pipeline stages" in CONFIG["deployment"]
    assert CONFIG["reference"] == "joyai_f32"
    assert 0 < CONFIG["reference_tolerance"] < 1
    assert "control.py" in CONFIG["reference_tolerance_why"]


# -- a toy of the family through the harness, from files alone ----------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, `tinyjoyai.longthink`, made by
    adding files and entries (tests/servebench/files/ holds the toy's
    configuration and traffic; the reference is the benchmark's own)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    for sub, name in (("configs", "tiny-joyai.json"),
                      ("traffic", "tinylongthink.json")):
        shutil.copy(FILES / sub / name, root / "servebench" / sub / name)
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-joyai", "source": "tests only",
                         "file": "servebench/configs/tiny-joyai.json",
                         "reduced": [], "why": "a toy for the CPU"})
    m["workloads"].append({"name": "tinyjoyai.longthink",
                           "config": "tiny-joyai", "traffic": "tinylongthink",
                           "chips": 1, "why": "closed loop on a toy"})
    for e in m["per_layer"]:
        if e["name"].startswith("latent_"):
            e["workloads"].append("tinyjoyai.longthink")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_a_toy_of_the_family_runs_from_added_files_alone(checkout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"),
               JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, str(checkout / "servebench" / "run.py"),
         "--workload", "tinyjoyai.longthink", "--seed", str(2 ** 31 + 44),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = [json.loads(ln) for ln in r.stdout.splitlines()
                 if ln.strip()][-2:]
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    ref = info["refcheck"]
    assert ref["ok"] and ref["max_err"] < 1e-4
    assert ref["reference"] == "joyai_f32"
    # the counter reached the line, and agrees with the clients' own
    # count to within the streams in prefill phase; the device's metrics
    # did not (a rehearsal prints none)
    rows = out["metrics"]["latent_rows_per_step"]["value"]
    assert 3 * 8 <= rows <= 3 * 4 * 128
    assert "latent_attn_share" not in out["metrics"]
    seen = info["latent_rows_per_step"]
    assert seen["counted"] == rows and seen["streams"] <= 4
    assert 0.4 * seen["expected"] <= rows <= 1.6 * seen["expected"] + 3 * 128
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    blocks = [t for t in ticks if t["latent_rows"] is not None]
    assert blocks and all(t["latent_steps"] % 2 == 0 for t in blocks)
    # the ready line and /health name the pool
    log = next((checkout / "chiprun_out").rglob("server.log")).read_text()
    assert "pool=latent" in log
