"""What PR 41 adds to the benchmark, on records written out by hand: the
least time of the Mamba-2 mixers at the cell's sizes
(`servebench/peaks.py:ssm_least_seconds` over the counts of
`servebench/ssm_peaks.py`), its three readers, the traffic file
`rollout.json`, the configuration file and the entries in the manifest;
and a toy of the family through the harness on the CPU (a rehearsal),
added from files alone."""
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

from servebench import peaks, ssm_peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELL = Cell(MANIFEST, "granite4h.rollout", ROOT)
CONFIG = CELL.config
V5E = "TPU v5 lite"
FILES = Path(__file__).resolve().parent / "files"


# -- the least time, worked by hand at the cell's sizes ----------------------

def test_sizes_of_one_mixer_and_of_one_stream_s_state():
    # Di 128 x 64; Dc Di + 2 x 1 x 128; P 2 x Di + 256 + 128 heads
    assert ssm_peaks.sizes(CONFIG) == {"inner": 8192, "conv": 8448,
                                       "proj": 16768}
    assert ssm_peaks.mamba_layers(CONFIG) == 9
    # 4,096 x 16,768 in and 8,192 x 4,096 out: ISSUE 41's 68.68 M + 33.55 M
    assert 4096 * 16768 == 68_681_728 and 8192 * 4096 == 33_554_432
    assert ssm_peaks.proj_params(CONFIG) == 102_236_160
    # a head's state [64, 128] x 128 heads, and 3 inputs of 8,448 channels
    assert ssm_peaks.state_values(CONFIG) == 1_048_576 + 25_344 == 1_073_920
    # what a slot holds: 9 layers in bf16 (cache/ssm_state.py says the same)
    assert 9 * 1_073_920 * 2 == 19_330_560


def test_least_time_of_a_block_by_hand():
    """128 live streams, 4 steps: per layer and step the projections'
    int8 codes once and 128 states read and written in bf16; 9 layers."""
    got = peaks.ssm_least_seconds(CONFIG, V5E, 1, 4, 128)
    layer_step = 102_236_160 + 128 * 1_073_920 * 2 * 2
    assert layer_step == 652_083_200
    assert got["bytes"] == 4 * 9 * layer_step == 23_474_995_200
    assert got["memory_s"] == pytest.approx(23_474_995_200 / 819e9)
    rows = 128 * (2 * 102_236_160 + 6 * 1_048_576)
    assert got["flops"] == 4 * 9 * rows == 971_199_479_808
    assert got["least_s"] == got["memory_s"] > got["compute_s"]
    assert got["bound"] == "memory"
    # the state is most of it: 84 % of the bytes at 128 streams, and a
    # lone stream reads the projections and little else
    one = peaks.ssm_least_seconds(CONFIG, V5E, 1, 1, 1)
    assert one["bytes"] == 9 * (102_236_160 + 4 * 1_073_920)
    # twice the chips, half the time; bf16 weights, two bytes a parameter
    two = peaks.ssm_least_seconds(CONFIG, V5E, 2, 4, 128)
    assert two["memory_s"] == pytest.approx(got["memory_s"] / 2)
    bf16 = dict(CONFIG, serve=dict(CONFIG["serve"], quant="none"))
    assert peaks.ssm_least_seconds(bf16, V5E, 1, 1, 0)["bytes"] == \
        9 * 2 * 102_236_160
    # the functions the whole step's count adds up too (peaks.step_parts)
    assert ssm_peaks.state_bytes(CONFIG) == 4 * 1_073_920
    assert ssm_peaks.state_flops(CONFIG) == 6 * 1_048_576
    assert [ssm_peaks.is_mamba(CONFIG, l) for l in range(10)] == \
        [True] * 5 + [False] + [True] * 4


def test_a_configuration_cut_in_depth_counts_the_layers_it_runs():
    """`layer_types` stays the source's list of 40; the file runs ten."""
    assert len(CONFIG["layer_types"]) == 40
    assert CONFIG["layer_types"].count("mamba") == 36
    whole = dict(CONFIG, num_hidden_layers=40)
    assert ssm_peaks.mamba_layers(whole) == 36
    assert ssm_peaks.mamba_layers(dict(CONFIG, num_hidden_layers=5)) == 5
    dense = {k: v for k, v in CONFIG.items() if k != "layer_types"}
    assert ssm_peaks.mamba_layers(dense) == 0


# -- the mixers' operations, by the shapes of their results ------------------

#: names as a traced run of the cell showed them (my chip run, PR 41,
#: seed 2147484607): the state's update in place, its readout y, the
#: in-projection, the conv's view and tail, a chunk's step of the scan
SSM_OPS = [
    "_fusion.786___bf16_9_128_128_64_128__4_3_2_1_0:T_8_128__2_1___fu",
    "_fusion.773___f32_128_128_64__2_1_0:T_8_128_S_1___fusion_f32_128",
    "_fusion.769___bf16_128_1_16768__2_0_1:T_8_128__2_1_S_1___fusion_",
    "_fusion.922___bf16_160_1_16768__2_0_1:T_8_128__2_1_S_1___fusion_",
    "_fusion.788___bf16_384_8448__1_0:T_8_128__2_1_S_1___fusion_bf16_",
    "_copy.249___bf16_128_4_8448__2_1_0:T_4_128__2_1_S_1___copy_bf16_",
    "_multiply_reduce_fusion.12____f32_128_64__1_0:T_8_128_S_1____f32",
    "_copy.247___f32_128_128_64__0_2_1:T_8_128_S_1___copy_f32_128_128",
    "_fusion.103___bf16_160_1_8192__2_1_0:T_8_128__2_1___fusion_f32_1",
]
#: the same run's other layers: the paged kernel, the experts, the
#: shared expert, the head, the window's copy, the out-projection's
#: result with the norm after it (not told from another layer's)
OTHER_OPS = [
    "_paged_attention.12___bf16_128_32_128__2_1_0:T_8_128__2_1_S_1___",
    "_fusion.784___bf16_128_4096__1_0:T_8_128__2_1_S_1___fusion_s8_10",
    "_fusion.783___bf16_72_128_1_768__3_1_0_2:T_8_128__2_1_S_1___fusi",
    "_fusion.936___bf16_72_160_768__2_1_0:T_8_128__2_1_S_1___fusion_s",
    "_convolution_multiply_fusion.2___bf16_128_100352__1_0:T_8_128__2",
    "_fusion.779___bf16_128_1536__1_0:T_8_128__2_1_S_1___fusion_s8_10",
    "_copy.242___bf16_128_8_256_128__3_2_1_0:T_8_128__2_1___copy_bf16",
    "_fusion.775____f32_128__0:T_128_S_1____bf16_128_1_4096__2_0_1:T_",
    "_while.1____s32___:T_128____bf16_1_18433_8_16_128__4_3_2_1_0:T_8",
    "_iota_reduce_fusion.4____bf16_128__0:T_256__128__2_1____s32_128_",
]


@pytest.mark.parametrize("name", SSM_OPS)
def test_an_operation_of_a_mixer_is_told_by_its_shape(name):
    assert ssm_peaks.ssm_patterns(CONFIG).search(name)


@pytest.mark.parametrize("name", OTHER_OPS)
def test_an_operation_of_another_layer_is_left_out(name):
    assert not ssm_peaks.ssm_patterns(CONFIG).search(name)


# -- the three readers --------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


def traced_ctx():
    """A capture of 2.0 s: seven runs of the mixed block (the first cut
    by the capture's start, the last ending with it) and 0.8 s in the
    mixers' operations."""
    ops = [[SSM_OPS[0], 0.3, 100], [SSM_OPS[1], 0.5, 100],
           [OTHER_OPS[0], 0.5, 100], [OTHER_OPS[2], 0.3, 10]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_mixed_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    streams = [stream(100, 0.0, 30), stream(200, 0.0, 30),
               stream(64, 0.0, 300), stream(250, 5.0, 10),
               stream(90, 0.0, 5, end=0.6)]
    return SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                           device={"kind": V5E}, streams=streams,
                           trace_at=2.95)


def test_ssm_share_on_a_trace_written_by_hand():
    assert CELL.reader("ssm_share")(traced_ctx()) == \
        pytest.approx(100 * 0.8 / 1.6)


def test_ssm_roofline_on_a_trace_written_by_hand():
    """Three streams generate at the trace's middle; the mixers took 0.8
    of the 1.9 s of block runs, so 0.3 x 0.8 / 1.9 of a whole block."""
    least = peaks.ssm_least_seconds(CONFIG, V5E, 1, 4, 3)["least_s"]
    assert CELL.reader("ssm_roofline")(traced_ctx()) == \
        pytest.approx(100 * least / (0.3 * 0.8 / 1.9))


@pytest.mark.parametrize("metric", ["ssm_share", "ssm_roofline"])
def test_nothing_to_read_is_none_and_never_raises(metric):
    read = CELL.reader(metric)
    ctx = traced_ctx()
    assert read(SimpleNamespace(**{**vars(ctx), "trace": {}})) is None
    assert read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    # a configuration without Mamba layers (another cell's)
    other = {k: v for k, v in CONFIG.items()
             if not k.startswith("mamba_") and k != "layer_types"}
    assert read(SimpleNamespace(**{**vars(ctx), "config": other})) is None


def tick(seq, rows, steps, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "ssm_rows": rows,
            "ssm_steps": steps}


def test_ssm_rows_per_step_on_tick_records_written_by_hand():
    read = CELL.reader("ssm_rows_per_step")
    ctx = SimpleNamespace(w0=50.0, w1=150.0, wall_minus_mono=0.0, ticks=[
        tick(1, 4 * 127 + 32 + 17, 4),        # two chunks in four steps
        tick(2, 4 * 128, 4), tick(2, 4 * 128, 4),   # polled twice
        tick(3, 8 * 126 + 64, 8),             # a tick that drained two
        tick(4, None, None),                  # a tick that drained none
        tick(5, 9000.0, 4, t_wall=10.0)])     # before the window
    assert read(ctx) == pytest.approx((557 + 512 + 1072) / 16)
    ctx.ticks = [tick(1, None, None), {"seq": 2, "t_wall": 100.0}]
    assert read(ctx) is None                  # the parent's records


# -- the traffic file ---------------------------------------------------------

def plan_of(seed):
    return make_plan(load_traffic(CELL.traffic_path), seed, 45.0,
                     CONFIG["vocab_size"], CONFIG["serve"]["max_seq"])


def test_rollout_is_one_multiset_under_three_seeds():
    plans = [plan_of(s) for s in (1, 2 ** 31 + 11, 2147500003)]
    shape = [[(len(r.tokens), r.max_tokens) for r in q]
             for q in plans[0].queues]
    for p in plans[1:]:
        assert [[(len(r.tokens), r.max_tokens) for r in q]
                for q in p.queues] == shape
    assert plans[0].queues[0][0].tokens != plans[1].queues[0][0].tokens
    p = plans[0]
    assert (p.kind, len(p.queues), p.lead_finished, p.lead_max_s,
            p.edge_quiet_s, p.late_limit_ms) == ("closed", 128, 32, 240.0,
                                                 0.05, 50.0)
    assert all(len(q) == 11 for q in p.queues)


def test_rollout_s_first_wave_is_staggered_and_every_request_fits():
    p = plan_of(7)
    firsts = sorted(q[0].max_tokens for q in p.queues)
    assert firsts == [16 * (c + 1) for c in range(128)]
    later = [r for q in p.queues for r in q[1:]]
    assert {r.max_tokens for r in later} == {2048}
    prompts = sorted(len(q[1].tokens) for q in p.queues)    # one round
    assert 64 <= prompts[0] < 66 and 250 < prompts[-1] <= 256
    assert 125 <= prompts[64] <= 131            # the median, log-uniform
    assert all(len(r.tokens) + r.max_tokens <= 2304
               for q in p.queues for r in q)
    # a closed loop that no step this model's bytes allow can run dry:
    # ten whole requests a client beyond the first
    assert min(sum(r.max_tokens for r in q[1:]) for q in p.queues) == 20_480


# -- the manifest's entries and the configuration file ------------------------

def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, unit, source, layer, better, moves in (
            ("ssm_share", "%", "device_trace", "kernels (ops/)", "lower",
             "tpot_p50_ms"),
            ("ssm_roofline", "%", "device_trace", "kernels (ops/)", "higher",
             "tpot_p50_ms"),
            ("ssm_rows_per_step", "rows", "program_counter",
             "cache manager (cache/)", "higher", "out_tok_s")):
        assert by[name] == {"name": name, "unit": unit, "better": better,
                            "source": source, "layer": layer, "moves": moves,
                            "workloads": ["granite4h.rollout"]}
    cfg = next(c for c in MANIFEST["configs"]
               if c["name"] == "granite-4.0-h-small")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == CONFIG["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    cell = next(w for w in MANIFEST["workloads"]
                if w["name"] == "granite4h.rollout")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("granite-4.0-h-small", "rollout", 1)
    assert [w["name"] for w in MANIFEST["workloads"]][-1] == cell["name"]
    # since PR 43 the cell reports the two expert counters too
    for name in ("experts_touched_share", "expert_rows_skew"):
        assert by[name]["workloads"] == ["smallthinker21b.batch",
                                         "keye30b.think", "granite4h.rollout"]
    assert {m["name"] for m in CELL.per_layer} >= {
        "ssm_share", "ssm_roofline", "ssm_rows_per_step", "block_roofline",
        "flush_ms_p50", "paged_attn_share", "mixed_block_ms_p50",
        "slot_occupancy", "starved_share", "experts_touched_share",
        "expert_rows_skew"}
    # a run whose state quietly took the jnp step is not correct
    assert CONFIG["kernels_must_hold"] == ["paged_win", "ssm_step"]
    assert "XLA's own operations" not in CONFIG["kernels_must_hold_why"]
    assert {m["name"] for m in CELL.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}


def test_the_file_holds_every_published_key_and_its_bytes():
    cat = json.loads((ROOT / "servebench/pins/granite-4.0-h-small.json")
                     .read_text())["published"]
    for key, value in cat.items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 10 >= 4
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    # one whole period: five Mamba layers, the attention layer, four more
    assert CONFIG["model"]["layer_types"] == CONFIG["layer_types"][:10] == \
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert CONFIG["layer_types"] == CONFIG["model"]["layer_types"] * 4
    assert CONFIG["serve"] == {
        "quant": "int8", "kv_quant": "none", "max_batch": 128,
        "max_seq": 2304, "page_size": 16, "decode_steps_per_tick": 4}
    # ISSUE 41's arithmetic: codes of a layer's experts, shared expert,
    # mixer and attention; a token of keys and values in ONE layer
    experts, shared = 72 * 3 * 4096 * 768, 3 * 4096 * 1536
    attn = 4096 * 128 * (32 + 8 + 8) + 32 * 128 * 4096
    assert (experts, shared, attn) == (679_477_248, 18_874_368, 41_943_040)
    codes = 10 * (experts + shared) + 9 * 102_236_160 + attn + 100352 * 4096
    assert codes == 8_356_626_432       # a step's weight bytes, less routers
    assert 2 * 8 * 128 * 2 == 4096 and 128 * 2304 * 4096 == 1_207_959_552
    assert 128 * 19_330_560 == 2_474_311_680
    assert set(CONFIG["assumed"]) >= {
        "head_dim", "n_shared_experts", "state_dtype", "expert_split",
        "gated_norm", "layer_types", "torch_dtype"}


@pytest.mark.parametrize("context", [500, 1164, 2000])
def test_block_roofline_s_count_for_the_cell_is_peaks_py_s_own(context):
    """servebench/peaks.py counts by layer kind (PR 43): nine mixers'
    projections and ONE attention layer's, every live stream's state read
    and written in nine layers, keys and values in one. To the byte at 128
    streams, whatever the context; until PR 43 it counted ten attention
    layers, ten layers' keys and values and no state: 10.4 GB at context
    500 against these 13.6, equal only near 1,164."""
    live = 128
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, [context] * live)
    experts = 72 * 3 * 4096 * 768 * (1 - (1 - 10 / 72) ** 128)
    weights = 9 * 102_236_160 + 41_943_040 \
        + 10 * (4096 * 72 + 2 * 3 * 4096 * 768 + experts) + 100352 * 4096
    # the codes of the file's own arithmetic and the routers, a byte a
    # parameter as peaks.py counts every file's (ISSUE 43's 8,362,524,672
    # took the routers at two), 33 B short of every expert: 1,280 draws
    # of 72 are expected to miss one in 2e8
    assert weights == pytest.approx(8_356_626_432 + 10 * 4096 * 72, abs=40)
    assert got["parts"] == {
        "weights": pytest.approx(weights, rel=1e-12),
        "rows": live * context * 4096, "index_keys": 0.0,
        "state": 4_948_623_360}
    assert 128 * 9 * 1_073_920 * 2 * 2 == 4_948_623_360
    assert got["bytes"] == pytest.approx(
        weights + 4_948_623_360 + live * context * 4096, rel=1e-12)
    # the mixers' own count is the same functions: the parts of the whole
    mixers = peaks.ssm_least_seconds(CONFIG, V5E, 1, 1, live)["bytes"]
    assert mixers == 9 * 102_236_160 + got["parts"]["state"]
    assert {500: 13.57e9, 1164: 13.92e9, 2000: 14.36e9}[context] == \
        pytest.approx(got["bytes"], rel=1e-3)
    # the operations: a position meets ten experts and the shared one in
    # every layer; the state costs six a value, a row read 4 x 32 x 128
    meets = 9 * 102_236_160 + 41_943_040 \
        + 10 * (4096 * 72 + 12 * 3 * 4096 * 768) + 100352 * 4096
    assert peaks.matmul_params(CONFIG) == meets
    assert got["flops"] == 2.0 * meets * live \
        + live * context * 16_384 + 9 * live * 6 * 1_048_576
    assert got["bound"] == "memory"


def test_the_expert_readers_on_tick_records_of_the_cell_s_shape():
    """Six records of a traced run of this cell (my chip run, PR 42, seed
    2147490003), as `/debug/ticks` gave them: the file names its 72
    experts `num_local_experts`, the third of the names peaks.py reads E
    under; 125-160 rows of ten draws touch every one of them."""
    assert "num_experts" not in CONFIG and peaks.num_experts(CONFIG) == 72
    ticks = json.loads((FILES.parent / "recorded_ticks" / "granite4h.rollout.json")
                       .read_text())
    ctx = SimpleNamespace(config=CONFIG, ticks=ticks, wall_minus_mono=0.0,
                          w0=ticks[0]["t_wall"] - 1, w1=ticks[-1]["t_wall"] + 1)
    assert [t["experts_touched"] for t in ticks] == [72.0] * 6
    assert CELL.reader("experts_touched_share")(ctx) == 100.0
    rows_max = sum(t["expert_rows_max"] for t in ticks)
    rows_mean = sum(t["expert_rows_mean"] for t in ticks)
    assert rows_max == pytest.approx(177.85, abs=0.01)
    assert rows_mean == pytest.approx(116.007, abs=0.01)
    assert CELL.reader("expert_rows_skew")(ctx) == \
        pytest.approx(rows_max / rows_mean) == pytest.approx(1.533, abs=1e-3)
    # a file that names its experts by neither key has nothing to read
    bare = {k: v for k, v in CONFIG.items() if k != "num_local_experts"}
    ctx.config = bare
    assert CELL.reader("experts_touched_share")(ctx) is None


# -- a toy of the family through the harness, from files alone ----------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, `tinygranite.rollout`, made by
    adding files and entries (tests/servebench/files/ holds the toy's
    configuration and traffic; the reference is the benchmark's own)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    for sub, name in (("configs", "tiny-granite.json"),
                      ("traffic", "tinyrollout.json")):
        shutil.copy(FILES / sub / name, root / "servebench" / sub / name)
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-granite", "source": "tests only",
                         "file": "servebench/configs/tiny-granite.json",
                         "reduced": [], "why": "a toy for the CPU"})
    m["workloads"].append({"name": "tinygranite.rollout",
                           "config": "tiny-granite", "traffic": "tinyrollout",
                           "chips": 1, "why": "closed loop on a toy"})
    for e in m["per_layer"]:
        if e["name"].startswith("ssm_"):
            e["workloads"].append("tinygranite.rollout")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_a_toy_of_the_family_runs_from_added_files_alone(checkout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"),
               JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, str(checkout / "servebench" / "run.py"),
         "--workload", "tinygranite.rollout", "--seed", str(2 ** 31 + 41),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = [json.loads(ln) for ln in r.stdout.splitlines()
                 if ln.strip()][-2:]
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    ref = info["refcheck"]
    assert ref["ok"] and ref["max_err"] < 1e-4
    assert ref["reference"] == "granite_hybrid_f32"
    # the counter reached the line; the device's metrics did not (a
    # rehearsal prints none)
    rows = out["metrics"]["ssm_rows_per_step"]["value"]
    assert 1.0 <= rows <= 4 + 32 and "ssm_share" not in out["metrics"]
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    blocks = [t for t in ticks if t["ssm_rows"] is not None]
    assert blocks and all(t["ssm_steps"] % 2 == 0 for t in blocks)
    assert sum(t["state_resets"] for t in blocks) >= 4
    # the ready line and /health say what state a slot keeps
    log = next((checkout / "chiprun_out").rglob("server.log")).read_text()
    assert '"layers": 3' in log and "bytes_per_slot" in log
