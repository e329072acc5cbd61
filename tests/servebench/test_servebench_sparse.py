"""What PR 36 adds to the benchmark, on records written out by hand: the
least time of the sparse-attention path at the cell's sizes
(`servebench/peaks.py:sparse_least_seconds` over the counts of
`servebench/sparse_peaks.py`), its three readers, the traffic file
`think.json`, and the entries in the manifest; since PR 43 the whole
step's count of a selecting file, and the two expert readers on tick
records of the cell's shape."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import metrics, peaks, sparse_peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELL = Cell(MANIFEST, "keye30b.think", ROOT)
CONFIG = CELL.config
V5E = "TPU v5 lite"


# -- the least time, worked by hand at the cell's sizes ----------------------

def test_bytes_of_the_indexer_and_of_a_cached_position():
    # 2,048 x (16 x 64 + 1 x 64 + 16) parameters; 64 and 2 x 4 x 128 values
    assert sparse_peaks.indexer_params(CONFIG) == 2048 * 1104 == 2_260_992
    assert sparse_peaks.index_key_bytes(CONFIG) == 128
    assert peaks.cached_row_bytes(CONFIG) == 2048


def test_least_time_of_three_streams_by_hand():
    """Contexts 1,000, 3,000 and 7,000: 11,000 positions of index keys,
    1,000 + 2,048 + 2,048 selected rows, the indexer's weights once; 8
    layers, 4 steps, 819 GB/s."""
    got = peaks.sparse_least_seconds(CONFIG, V5E, 1, 4,
                                            [1000, 3000, 7000])
    per_layer_step = 11_000 * 128 + 5_096 * 2048 + 2_260_992 * 2
    assert per_layer_step == 16_366_592
    assert got["bytes"] == 8 * 4 * per_layer_step == 523_730_944
    assert got["selected_tokens"] == 5_096 and got["live_tokens"] == 11_000
    assert got["memory_s"] == pytest.approx(523_730_944 / 819e9)
    assert got["least_s"] == got["memory_s"] > got["compute_s"]
    # a context under topk reads all of itself; twice the chips, half the time
    short = peaks.sparse_least_seconds(CONFIG, V5E, 2, 1, [100])
    assert short["bytes"] == 8 * (100 * (128 + 2048) + 2_260_992 * 2)
    assert short["memory_s"] == pytest.approx(short["bytes"] / (2 * 819e9))


def test_the_cell_s_own_counts_are_issue_36_s():
    """32 streams of mean context 3,750: about 15 MB of index keys and
    132 MB of selected rows a layer and step, where the whole context's
    keys and values would be 246 MB."""
    got = peaks.sparse_least_seconds(CONFIG, V5E, 1, 1, [3750] * 32)
    layer = got["bytes"] / 8
    assert 32 * 3750 * 128 == 15_360_000
    assert 32 * 2048 * 2048 == 134_217_728
    assert layer == 15_360_000 + 134_217_728 + 4_521_984
    assert 32 * 3750 * 2048 == 245_760_000


def by_hand(contexts, experts_read):
    """One step of the file, bytes: int8 codes of 8 layers' attention
    (18,874,368), router and `experts_read` experts of 4,718,592, and of
    the head; per layer the indexer in bf16, min(c, 2,048) rows of 2,048 B
    and c index keys of 128 B a stream."""
    weights = 8 * (18_874_368 + 2048 * 128 + experts_read * 4_718_592) \
        + 151_936 * 2048 + 8 * 2_260_992 * 2
    rows = 8 * sum(min(c, 2048) for c in contexts) * 2048
    keys = 8 * sum(contexts) * 128
    return weights, rows, keys


@pytest.mark.parametrize("contexts", [[3750] * 32, [1000, 3000, 7000],
                                      [100, 2048, 2049], []])
def test_the_whole_step_counts_the_selection_not_the_context(contexts):
    """What `block_roofline` reads for this file: each layer reads
    min(context, topk) rows a stream and the whole context's index keys;
    the path's own count (`sparse_least_seconds`) is those same bytes."""
    n = max(1, len(contexts))
    weights, rows, keys = by_hand(contexts, 128 * (1 - (1 - 8 / 128) ** n))
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 4, contexts)
    assert got["parts"] == {"weights": pytest.approx(weights, rel=1e-12),
                            "rows": rows, "index_keys": keys, "state": 0.0}
    assert got["bytes"] == pytest.approx(4 * (weights + rows + keys),
                                         rel=1e-12)
    path = peaks.sparse_least_seconds(CONFIG, V5E, 1, 4, contexts)
    assert path["bytes"] == 4 * (rows + keys + 8 * 2_260_992 * 2)
    assert got["bound"] == "memory"


def test_the_cell_s_step_is_issue_43_s_6_48_gb():
    """32 streams of 3,750. The parent counted every layer at the WHOLE
    context, 1.97 GB of keys and values; the model reads 32 x 2,048 rows
    a layer (1.07 GB) and 0.12 GB of index keys: 0.89 of the parent's
    count, what `block_roofline` falls by. ISSUE 43's 7.27 -> 6.48 GB are
    these counts with ALL 128 experts streamed, as the program does;
    `peaks.py` counts the 111.77 that 32 x 8 even draws are expected to
    touch, as it does for every file."""
    contexts = [3750] * 32
    weights, rows, keys = by_hand(contexts, 128)
    assert (rows, keys) == (1_073_741_824, 122_880_000)
    whole = 8 * 32 * 3750 * 2048
    assert whole == 1_966_080_000
    indexers = 8 * 2_260_992 * 2
    assert weights - indexers + whole == pytest.approx(7.27e9, rel=0.002)
    assert weights + rows + keys == 6_528_892_928 == \
        pytest.approx(6.48e9, rel=0.01)
    touched = 128 * (1 - 0.9375 ** 32)
    assert touched == pytest.approx(111.771, abs=1e-3)
    weights, rows, keys = by_hand(contexts, touched)
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, contexts)
    assert got["bytes"] == pytest.approx(weights + rows + keys, rel=1e-12)
    assert got["bytes"] == pytest.approx(5.916e9, rel=1e-3)
    parents = weights - indexers + whole
    assert parents == pytest.approx(6.650e9, rel=1e-3)
    assert got["bytes"] / parents == pytest.approx(0.89, abs=0.002)
    # the operations: every position meets 8 experts; a row read costs
    # 4 x 32 heads x 128, a position scored 2 x 16 heads x 64
    meets = 8 * (18_874_368 + 2048 * 128 + 8 * 4_718_592 + 2_260_992) \
        + 151_936 * 2048
    assert peaks.matmul_params(CONFIG) == meets
    assert got["flops"] == 2.0 * meets * 32 + 8 * 32 * 2048 * 16_384 \
        + 8 * 32 * 3750 * 2048


# -- the path's operations, by the shapes of their results -------------------

#: names as a traced run of the cell showed them (my chip run, PR 36)
SPARSE_OPS = [
    "_fusion.582___bf16_262144_128__1_0:T_8_128__2_1___fusion_bf16_73",
    "_fusion.581___s32_65536__0:T_1024_S_1___fusion_s32_32_448__1_0:T",
    "_fusion.577___bf16_229376_64__1_0:T_8_128__2_1_S_1___fusion_bf16",
    "_copy_select_fusion.3____bf16_32_2048_4_128__3_1_2_0:T_8_128__2_",
    "_fusion.575___bf16_14336_16_64__2_1_0:T_8_128__2_1_S_1___fusion_",
    "_sort.41____f32_32_7168__1_0:T_8_128____s32_32_7168__1_0:T_8_128",
    "_fusion.590___f32_32_4_8_2304__3_2_1_0:T_8_128_S_1___fusion_bf16",
    "_reduce-window.72___s32_32_56_128__2_1_0:T_8_128_S_1___reduce-wi",
    "_reshape.2144___s32_262144__0:T_1024_S_1___reshape_s32_32_2048_4",
    "_fusion.563___bf16_448_4_16_128__3_2_1_0:T_8_128__2_1_S_1___fusi",
    "_copy.346___s32_32_2048_4__2_1_0:T_8_128_S_1___copy_s32_32_2048_",
    "_copy-done.15___pred_32_2048__1_0:T_8_128__4_1_S_1___copy-done__",
    # what PR 37's token-major pool moved out of the patterns' reach
    # (PERF.md section 7, PR 36 (d); my chip run, PR 42, seed 2147485301)
    "_fusion.660___pred_8192__0:T_1024__128__4_1_S_1___fusion_pred_32",
    "_fusion.614___bf16_448_16_512__2_1_0:T_8_128__2_1_S_1___fusion_b",
    "_fusion.653___f32_32_1_1_8_2304__4_3_0_2_1:T_8_128_S_1___fusion_",
    "_divide_convert_fusion.5___bf16_32_4_1_8_2304__4_3_0_2_1:T_8_128",
    "_fusion.663___f32_32_4_8__2_0_1:T_8_128_S_1___fusion_f32_32_1_1_",
    "_dynamic-slice_bitcast_fusion.18___bf16_32_256_512__2_1_0:T_8_12",
    "_fusion.632___bf16_32_256_64__2_1_0:T_8_128__2_1_S_1___fusion_bf",
    "_fusion.664___bf16_32_1_1_8_128__4_3_0_2_1:T_8_128__2_1_S_1___fu",
    "_fusion.642___bf16_1_32_1_8_128__4_3_1_2_0:T_8_128__2_1_S_1___fu",
    "_fusion.639___f32_4_32_8__2_1_0:T_8_128_S_1___fusion_f32_1_1_32_",
    "_copy.315___f32_4_8_56_128__3_2_1_0:T_8_128_S_1___copy_f32_4_8_5",
]
OTHER_OPS = [
    "_fusion.598___bf16_64_2048__1_0:T_8_128__2_1_S_1___fusion_s8_8_1",
    "_fusion.597___bf16_128_64_1_768__3_1_0_2:T_8_128__2_1_S_1___fusi",
    "_convolution_multiply_fusion.3___bf16_32_151936__1_0:T_8_128__2_",
    "_copy.412___bf16_8_32_4_256_128__4_2_3_1_0:T_4_128__2_1___copy_b",
    "_fusion.592___bf16_32_1_32_128__3_2_0_1:T_8_128__2_1_S_1___fusio",
    "_gather.270___bf16_32_2048__1_0:T_8_128__2_1___gather_bf16_15193",
    "_fusion.593____f32_64__0:T_128_S_1____bf16_64_1_2048__2_0_1:T_8_",
    "_fusion.300___bf16_32_1800_4_128__2_1_0:T_8_128__fusion_",
    # beside the new patterns: the window's copy inside every block (A4a),
    # an expert's rows, a mask of the rows of one step and of the flush
    "_copy.388___bf16_8_32_1_256_512__4_3_1_2_0:T_8_128__2_1___copy_b",
    "_fusion.616___bf16_64_512__1_0:T_8_128__2_1_S_1___fusion_s8_256_",
    "_fusion.621____bf16_32_1_32_128__3_0_2_1:T_8_128__2_1_S_1____bf1",
    "_copy-done.43___pred_64__0:T_512__128__4_1_S_1___copy-done__pred",
    "_compare_reduce_fusion.24____pred_544__0:T_1024__128__4_1_S_1___",
    "_multiply_reduce_fusion.10____f32_64_32__0_1:T_8_128_S_1____bf16",
]


@pytest.mark.parametrize("name", SPARSE_OPS)
def test_an_operation_of_the_path_is_told_by_its_shape(name):
    pats = sparse_peaks.sparse_patterns(CONFIG)
    assert sparse_peaks.is_sparse_op(name, pats)


@pytest.mark.parametrize("name", OTHER_OPS)
def test_an_operation_of_another_layer_is_left_out(name):
    pats = sparse_peaks.sparse_patterns(CONFIG)
    assert not sparse_peaks.is_sparse_op(name, pats)


# -- the three readers --------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


def traced_ctx():
    """A capture of 2.0 s: three runs of the mixed block (0.1 cut by the
    capture's start, then 0.3 and 0.3 whole... the last ends with the
    capture and is dropped too) and 1.0 s in the path's operations."""
    ops = [[SPARSE_OPS[0], 0.6, 100], [SPARSE_OPS[5], 0.4, 100],
           [OTHER_OPS[0], 0.5, 100], [OTHER_OPS[2], 0.1, 10]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_mixed_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    streams = [stream(1000, 0.0, 30), stream(3000, 0.0, 30),
               stream(6971, 0.0, 300), stream(500, 5.0, 10),
               stream(900, 0.0, 5, end=0.6)]
    return SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                           device={"kind": V5E}, streams=streams,
                           trace_at=2.95)


def test_sparse_attn_share_on_a_trace_written_by_hand():
    assert CELL.reader("sparse_attn_share")(traced_ctx()) == \
        pytest.approx(100 * 1.0 / 1.6)


def test_sparse_attn_roofline_on_a_trace_written_by_hand():
    """Three streams generate at the trace's middle, with 30, 30 and 30
    tokens received (contexts 1,030, 3,030, 7,001); the path took 1.0 of
    the 1.9 s of block runs, so 0.3 x 1.0 / 1.9 of a whole block."""
    ctx = traced_ctx()
    assert metrics.live_contexts(ctx.streams, ctx.trace_at) == \
        [1030, 3030, 7001]
    least = peaks.sparse_least_seconds(CONFIG, V5E, 1, 4,
                                       [1030, 3030, 7001])["least_s"]
    assert CELL.reader("sparse_attn_roofline")(ctx) == \
        pytest.approx(100 * least / (0.3 * 1.0 / 1.9))


@pytest.mark.parametrize("metric", ["sparse_attn_share",
                                    "sparse_attn_roofline"])
def test_nothing_to_read_is_none_and_never_raises(metric):
    read = CELL.reader(metric)
    ctx = traced_ctx()
    assert read(SimpleNamespace(**{**vars(ctx), "trace": {}})) is None
    assert read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    # a configuration without an indexer (the parent's, another cell's)
    other = {k: v for k, v in CONFIG.items() if k != "sa_config"}
    assert read(SimpleNamespace(**{**vars(ctx), "config": other})) is None


def tick(seq, live, selected, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "kv_rows_live": live,
            "kv_rows_selected": selected}


def test_kv_selected_share_on_tick_records_written_by_hand():
    read = CELL.reader("kv_selected_share")
    ctx = SimpleNamespace(w0=50.0, w1=150.0, wall_minus_mono=0.0, ticks=[
        tick(1, 4000.0, 2000.0), tick(2, 3000.0, 1500.0),
        tick(2, 3000.0, 1500.0),              # polled twice: counted once
        tick(3, 1000.0, 1000.0),
        tick(4, 0.0, 0.0),                    # a block with no decode row
        tick(5, None, None),                  # a tick that drained no block
        tick(6, 9000.0, 9.0, t_wall=10.0)])   # before the window
    assert read(ctx) == pytest.approx(100 * 4500 / 8000)
    ctx.ticks = [tick(1, None, None), {"seq": 2, "t_wall": 100.0}]
    assert read(ctx) is None                  # the parent's records


def test_the_expert_readers_on_tick_records_of_the_cell_s_shape():
    """Six records of a traced run of this cell (my chip run, PR 42, seed
    2147485301), as `/debug/ticks` gave them: 128 experts under the key
    `num_experts`, about 97 of them touched a step, and the fullest
    expert ten times the mean (the seeded weights route unevenly)."""
    ticks = json.loads((Path(__file__).parent / "recorded_ticks"
                        / "keye30b.think.json").read_text())
    ctx = SimpleNamespace(config=CONFIG, ticks=ticks, wall_minus_mono=0.0,
                          w0=ticks[0]["t_wall"] - 1, w1=ticks[-1]["t_wall"] + 1)
    seen = [96.8125, 96.6875, 96.8125, 97.21875, 97.1875, 96.8125]
    assert [t["experts_touched"] for t in ticks] == seen
    assert CELL.reader("experts_touched_share")(ctx) == \
        pytest.approx(100 * sum(seen) / 6 / 128)
    rows_max = [32.90625, 32.75, 32.84375, 32.71875, 32.46875, 32.375]
    assert CELL.reader("expert_rows_skew")(ctx) == \
        pytest.approx(sum(rows_max) / (6 * 3.25))
    # a window that holds none of them, and records without the counts
    ctx.w0 = ctx.w1
    assert CELL.reader("experts_touched_share")(ctx) is None
    assert CELL.reader("expert_rows_skew")(ctx) is None


# -- the traffic file ---------------------------------------------------------

def plan_of(seed):
    return make_plan(load_traffic(CELL.traffic_path), seed, 45.0,
                     CONFIG["vocab_size"], CONFIG["serve"]["max_seq"])


def test_think_is_one_multiset_under_three_seeds():
    plans = [plan_of(s) for s in (1, 2 ** 31 + 11, 2147500003)]
    shape = [[(len(r.tokens), r.max_tokens) for r in q]
             for q in plans[0].queues]
    for p in plans[1:]:
        assert [[(len(r.tokens), r.max_tokens) for r in q]
                for q in p.queues] == shape
    assert plans[0].queues[0][0].tokens != plans[1].queues[0][0].tokens
    p = plans[0]
    assert (p.kind, len(p.queues), p.lead_finished, p.lead_max_s,
            p.edge_quiet_s, p.late_limit_ms) == ("closed", 32, 16, 240.0,
                                                 0.05, 50.0)
    assert all(len(q) == 7 for q in p.queues)


def test_think_s_first_wave_is_staggered_and_every_request_fits():
    p = plan_of(7)
    firsts = sorted(q[0].max_tokens for q in p.queues)
    assert firsts == [96 * (c + 1) for c in range(32)]
    later = [r for q in p.queues for r in q[1:]]
    assert {r.max_tokens for r in later} == {3072}
    prompts = sorted(len(q[1].tokens) for q in p.queues)    # one round
    assert 1024 <= prompts[0] < 1100 and 3900 < prompts[-1] <= 4096
    assert 2000 <= prompts[16] <= 2100          # the median, log-uniform
    assert all(len(r.tokens) + r.max_tokens <= 7168
               for q in p.queues for r in q)


# -- the manifest's entries ---------------------------------------------------

def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, source, layer, better in (
            ("kv_selected_share", "program_counter",
             "cache manager (cache/)", "lower"),
            ("sparse_attn_share", "device_trace", "kernels (ops/)", "lower"),
            ("sparse_attn_roofline", "device_trace", "kernels (ops/)",
             "higher")):
        assert by[name] == {"name": name, "unit": "%", "better": better,
                            "source": source, "layer": layer,
                            "moves": "tpot_p50_ms",
                            "workloads": ["keye30b.think"]}
    cfg = next(c for c in MANIFEST["configs"]
               if c["name"] == "keye-vl2-30b-a3b")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == CONFIG["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    cell = next(w for w in MANIFEST["workloads"]
                if w["name"] == "keye30b.think")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("keye-vl2-30b-a3b", "think", 1)
    # since PR 43 the cell reports the two expert counters too
    for name in ("experts_touched_share", "expert_rows_skew"):
        assert "keye30b.think" in by[name]["workloads"]
    assert {m["name"] for m in CELL.per_layer} >= {
        "kv_selected_share", "sparse_attn_share", "sparse_attn_roofline",
        "flush_ms_p50", "paged_attn_share", "mixed_block_ms_p50",
        "experts_touched_share", "expert_rows_skew"}


def test_the_file_holds_every_published_key_and_its_bytes():
    cat = json.loads((ROOT / "servebench/pins/keye-vl2-30b-a3b.json")
                     .read_text())["published"]
    for key, value in cat.items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 8 >= 4
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    assert CONFIG["sa_config"]["topk"] == 2048
    assert CONFIG["model"]["index_topk"] == CONFIG["sa_config"]["topk"]
    # ISSUE 36's arithmetic: int8 codes of a layer's experts and attention
    experts = 128 * 3 * 2048 * 768
    attn = 2048 * 128 * (32 + 4 + 4) + 32 * 128 * 2048
    assert (experts, attn) == (603_979_776, 18_874_368)
    token = 8 * (2 * 4 * 128 * 2 + 64 * 2)
    assert token == 17_408 and 32 * 7168 * token == 3_992_977_408
    assert set(CONFIG["assumed"]) >= {
        "text_only", "qk_norm", "indexer_input", "indexer_key_norm",
        "indexer_rope", "indexer_score"}
