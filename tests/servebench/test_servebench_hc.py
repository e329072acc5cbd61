"""What PR 49 adds to the benchmark, on records written out by hand: the
least time of the mixing of a model's n residual streams at the cell's
sizes (`servebench/hc_peaks.py`), how a trace tells the mixing's
operations, its three readers, the configuration file and the entries
in the manifest; `servebench/peaks.py`'s own count of the new file (two
leading dense layers, 64 experts of 1,024, the latent row) against hand
counts; and a toy of the family through the harness on the CPU (a
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

from servebench import hc_peaks, latent_peaks, peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELL = Cell(MANIFEST, "xing29b.rollout", ROOT)
CONFIG = CELL.config
V5E = "TPU v5 lite"
FILES = Path(__file__).resolve().parent / "files"
SIX = ["mistral7b.batch", "mistral7b-bf16-tp4.batch", "smallthinker21b.batch",
       "keye30b.think", "granite4h.rollout", "joyai48b.longthink"]


# -- the least time, worked by hand at the cell's sizes ----------------------

def test_sizes_of_one_rows_streams_and_of_one_mixing_projection():
    assert hc_peaks.has_streams(CONFIG)
    assert hc_peaks.mix_width(CONFIG) == 4 + 4 + 16 == 24
    assert hc_peaks.sublayers(CONFIG) == 20
    # four streams of 3,584 bf16 values, read once and written once
    assert hc_peaks.row_bytes(CONFIG) == 2 * 4 * 3584 * 2 == 57_344
    assert hc_peaks.phi_bytes(CONFIG) == 14336 * 24 * 2 == 688_128
    # the projection, the read, the write, 20 rounds over 4 x 4
    assert hc_peaks.row_flops(CONFIG) == 2 * 14336 * 24 + 2 * 14336 \
        + 2 * 4 * 5 * 3584 + 4 * 16 * 20 == 861_440
    assert not hc_peaks.has_streams(
        Cell(MANIFEST, "joyai48b.longthink", ROOT).config)


@pytest.mark.parametrize("rows", [64, 128])
def test_the_least_time_of_a_step_s_mixing_by_hand(rows):
    """20 sublayers: each reads and writes its rows' streams once and
    phi once. 128 rows: 20 x (128 x 57,344 + 688,128) = 160.6 MB, 196 us
    of 819 GB/s; 15 operations a byte, far under the chip's 240."""
    got = hc_peaks.hc_least_seconds(CONFIG, V5E, 1, 1, rows)
    assert got["parts"] == {"streams": 20 * rows * 57_344,
                            "phi": 20 * 688_128}
    assert got["bytes"] == 20 * (rows * 57_344 + 688_128)
    assert got["bytes"] == {64: 87_162_880, 128: 160_563_200}[rows]
    assert got["flops"] == 20 * rows * 861_440
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(got["bytes"] / 819e9)
    assert got["least_s"] == pytest.approx(
        {64: 106.4e-6, 128: 196.0e-6}[rows], rel=1e-3)
    four = hc_peaks.hc_least_seconds(CONFIG, V5E, 1, 4, rows)
    assert four["least_s"] == pytest.approx(4 * got["least_s"])


def test_peaks_py_counts_the_new_file_by_hand():
    """`servebench/peaks.py` reads every key this file has: the five
    latent projections, TWO leading dense layers of 9,216, 8 layers of
    64 experts of 1,024 with a shared one and a router, the head; a row
    of 1,152 B. ISSUE 49's step: 128 streams at the mean context 1,164
    move 6.7 GB of weights and 1.7 GB of rows, least 10.3 ms. It does
    NOT count the mixing (161 MB a step, 2 %: hc_peaks.py)."""
    attn = peaks.attention_params(CONFIG)
    assert attn == 3584 * 768 + 768 * 32 * 192 + 3584 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 3584 == 28_409_856
    dense, expert = 3 * 3584 * 9216, 3 * 3584 * 1024
    assert (dense, expert, 3584 * 64) == (99_090_432, 11_010_048, 229_376)
    assert peaks.num_experts(CONFIG) == 64
    assert peaks.cached_row_bytes(CONFIG) == 1152
    assert latent_peaks.is_latent(CONFIG)
    head = 131072 * 3584
    # every expert is touched at 128 rows of 4: 64 x (1 - (15/16)^128)
    assert peaks.streamed_params(CONFIG, 128) == pytest.approx(
        10 * attn + 2 * dense + 8 * (229_376 + 65 * expert) + head,
        rel=1e-3)
    assert peaks.matmul_params(CONFIG) == 10 * attn + 2 * dense \
        + 8 * (229_376 + 5 * expert) + head
    parts, fl = peaks.step_parts(CONFIG, [1164] * 128)
    assert parts["weights"] == pytest.approx(6.68e9, rel=2e-3)
    assert parts["rows"] == 128 * 10 * 1164 * 1152 == 1_716_387_840
    assert parts["index_keys"] == parts["state"] == 0
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, [1164] * 128)
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(10.25e-3, rel=5e-3)
    mixing = hc_peaks.hc_least_seconds(CONFIG, V5E, 1, 1, 128)
    assert mixing["bytes"] / got["bytes"] == pytest.approx(0.019, abs=0.001)


# -- the mixing's operations in a trace --------------------------------------

#: the mixing as a traced run of the cell named it (my chip run, PR 49,
#: seed 2147493101): the write, one stream a result (half the path's
#: time), phi sliced by layer and relaid for the product, the streams'
#: float32 copy and its prefetch, the embedding's fan-out, the
#: projection's result and its res~ part; at 128 and 160 rows
HC_OPS = [
    "_fusion.871____bf16_1_128_1_3584__3_1_2_0:T_8_128__2_1_S_1____bf",
    "_fusion.1058____bf16_1_160_1_3584__3_1_2_0:T_8_128__2_1_S_1____b",
    "_multiply_convert_fusion.5___bf16_1_128_1_3584__3_1_2_0:T_8_128_",
    "_pad_maximum_fusion.6___bf16_4_128_1_3584__3_1_2_0:T_8_128__2_1_",
    "_constant_dynamic-slice_fusion.31___bf16_1_14336_24__1_2_0:T_8_1",
    "_reshape.93.clone.11___bf16_4_3584_24__1_0_2:T_4_128__2_1_S_1___",
    "_convert_element_type.1521___f32_4_128_1_3584__3_1_2_0:T_8_128_S",
    "_maximum_convert_fusion.5___f32_4_160_1_3584__3_1_2_0:T_8_128_S_",
    "_copy-done___f32_4_160_1_3584__3_1_2_0:T_8_128___copy-done__f32_",
    "_broadcast_in_dim.746___bf16_4_128_1_3584__3_1_2_0:T_8_128__2_1_",
    "_fusion.843___f32_24_128__1_0:T_8_128_S_1___fusion_bf16_4_128_1_",
    "_fusion.1022___f32_16_160__1_0:T_8_128_S_1___fusion_f32_24_160__",
]
#: the mixing's own operations that the pattern does NOT tell, since a
#: counter's or a router's result has their shapes too: the norm's sum
#: of squares [R], b [K], H_pre and H_post ([n, R], [n, R]), a round's
#: clipped entries [1, n, R], the Sinkhorn's fused rounds (tuples of
#: float32 vectors of rows): a fifth of the mixing's time in that run
HC_OPS_NOT_TOLD = [
    "_multiply_reduce_fusion.22___f32_128__0:T_128_S_1___fusion_f32_4",
    "_multiply_reduce_fusion.22___f32_160__0:T_256_S_1___fusion_f32_4",
    "_fusion.873___f32_24__0:T_128_S_1___fusion_bf16_10_24__1_0:T_8_1",
    "_select_select_fusion.7____f32_24__0:T_128_S_1____f32_24__0:T_12",
    "_fusion.875____f32_4_128__1_0:T_4_128_S_1____f32_4_128__1_0:T_4_",
    "_maximum_bitcast_fusion.57___f32_1_4_128__2_1_0:T_4_128_S_1___fu",
    "_multiply_multiply_fusion.2358____f32_1_128__1_0:T_1_128_S_1____",
    "_multiply_divide_fusion.111____f32_1_160__1_0:T_1_128_S_1____f32",
    "_add_add_fusion.1070____f32_1_128__1_0:T_1_128_S_1____f32_1_128_",
    "_slice_bitcast_fusion.153____f32_128__0:T_128_S_1____f32_128__0:",
    "_fusion.1081____f32_1_1_160__2_1_0:T_1_128____f32_1_1_160__2_1_0",
]
#: the same run's other operations: an expert product, a latent
#: projection fused behind its norm's sum (a tuple that STARTS as the
#: Sinkhorn's do), that norm's rsqrt and sum, the router's scores, the
#: experts' counter (a vector beside its scalar sum), the head, the
#: window's stage, the latent call, an activation [R, C] (the read's
#: result h is one too, and is not caught), the sampler's pair of a value
#: and an index, the counters; and the LAYER SCAN itself, a `while`
#: whose carry holds the streams (its self time is the loop's)
OTHER_OPS = [
    "_fusion.882___bf16_64_128_1024__2_1_0:T_8_128__2_1_S_1___fusion_",
    "_fusion.781____f32_128__0:T_128_S_1____bf16_128_768__1_0:T_8_128",
    "_fusion.939____f32_160__0:T_256_S_1____bf16_160_768__1_0:T_8_128",
    "_add_rsqrt_fusion.4___f32_160__0:T_256_S_1___fusion__multiply_r",
    "_fusion.12___f32_128__0:T_128_S_1___fusion__fusion___",
    "_fusion.77___f32_64__0:T_128_S_1___fusion__get-tuple-",
    "_select_reduce_fusion.12____f32_64__0:T_128_S_1____f32___:T_128_",
    "_broadcast_add_fusion___f32_160__0:T_256_S_1___fus",
    "_convolution_multiply_fusion.2___bf16_128_131072__1_0:T_8_128__2",
    "_stage_window___bf16_10_128_1_256_640__4_3_2_1_0:T_8_128__2_1___",
    "_latent_attention.26___bf16_128_32_512__2_1_0:T_8_128__2_1_S_1__",
    "_fusion.886___bf16_128_3584__1_0:T_8_128__2_1_S_1___fusion_bf16_",
    "_fusion.5____f32_128__0:T_128_S_1____s32_128__0:T_128_S_1____fus",
    "_fusion.3___f32_3__0:T_128_S_1___fusion__get-tuple-element",
    "_multiply_reduce_fusion.8___f32_3__0:T_128_S_1___fusion_f32_4_5_",
    "_fusion.9___f32_4_5__1_0:T_4_128___fusion_",
    "_while.78____s32___:T_128____bf16_4_128_1_3584__3_1_2_0:T_8_128_",
    "_paged_attention.12___bf16_128_32_128__2_1_0:T_8_128__2_1_S_1___",
]


@pytest.mark.parametrize("name", HC_OPS)
def test_the_mixing_is_told_by_the_shapes_of_its_results(name):
    assert hc_peaks.hc_patterns(CONFIG).search(name)


@pytest.mark.parametrize("name", OTHER_OPS + HC_OPS_NOT_TOLD)
def test_another_operation_is_left_out(name):
    assert not hc_peaks.hc_patterns(CONFIG).search(name)


def test_the_pattern_is_made_from_the_file_s_sizes():
    """Another model's sizes give another pattern: two streams of 64
    match [2, R, 1, 64] and [8, R], and none of this cell's names."""
    toy = dict(hc_mult=2, hidden_size=64)
    pat = hc_peaks.hc_patterns(toy)
    assert pat.search("_fusion.3___bf16_2_24_1_64__3_1_2_0")
    assert pat.search("_fusion.4___f32_8_24__1_0")
    assert not pat.search(HC_OPS[0]) and not pat.search(HC_OPS[10])


# -- the three readers --------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


def traced_ctx():
    """A capture of 2.0 s: seven runs of the mixed block (the first cut
    by the capture's start, the last ending with it) and 0.4 s in the
    mixing's operations."""
    ops = [[HC_OPS[0], 0.2, 600], [HC_OPS[4], 0.15, 7200],
           [HC_OPS[10], 0.05, 200], [HC_OPS_NOT_TOLD[6], 0.02, 7200],
           [OTHER_OPS[0], 0.9, 100],
           [OTHER_OPS[10], 0.3, 100], [OTHER_OPS[16], 0.05, 20]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_mixed_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    streams = [stream(100, 0.0, 30), stream(200, 0.0, 30),
               stream(64, 0.0, 300), stream(125, 5.0, 10),
               stream(90, 0.0, 5, end=0.6)]
    return SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                           device={"kind": V5E}, streams=streams,
                           trace_at=2.95, info={})


def test_hc_share_on_a_trace_written_by_hand():
    assert CELL.reader("hc_share")(traced_ctx()) == \
        pytest.approx(100 * 0.4 / 1.6)


def test_hc_roofline_on_a_trace_written_by_hand():
    """Three streams generate at the trace's middle; the mixing took
    0.4 of the 1.9 s of block runs, so 0.3 x 0.4 / 1.9 of a whole block
    of four steps (the file's `decode_steps_per_tick`)."""
    ctx = traced_ctx()
    least = hc_peaks.hc_least_seconds(CONFIG, V5E, 1, 4, 3)
    assert least["bytes"] == 4 * 20 * (3 * 57_344 + 688_128)
    got = CELL.reader("hc_roofline")(ctx)
    assert got == pytest.approx(100 * least["least_s"] / (0.3 * 0.4 / 1.9))
    assert 0 < got < 100
    assert ctx.info["hc_roofline"]["rows"] == 3


def test_block_roofline_takes_the_steps_of_a_block_from_the_file():
    """The whole block's share at the file's four steps a block: three
    streams generate at the trace's middle (contexts 130, 230 and 94,
    less their prompts' difference), five whole runs of 0.3 s. A file
    that stated eight would be held to twice the least time."""
    ctx = traced_ctx()
    assert CONFIG["serve"]["decode_steps_per_tick"] == 4
    least = peaks.block_least_seconds(CONFIG, V5E, 1, 4, [130, 230, 94])
    assert CELL.reader("block_roofline")(ctx) == \
        pytest.approx(100 * least["least_s"] / 0.3)
    assert ctx.info["block_roofline"]["contexts"] == [130, 230, 94]
    eight = dict(CONFIG, serve=dict(CONFIG["serve"], decode_steps_per_tick=8))
    assert CELL.reader("block_roofline")(
        SimpleNamespace(**{**vars(ctx), "config": eight})) == \
        pytest.approx(200 * least["least_s"] / 0.3)


@pytest.mark.parametrize("metric", ["hc_share", "hc_roofline"])
def test_nothing_to_read_is_none_and_never_raises(metric):
    read = CELL.reader(metric)
    ctx = traced_ctx()
    assert read(SimpleNamespace(**{**vars(ctx), "trace": {}})) is None
    assert read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    bare = dict(ctx.trace, ops=[o for o in ctx.trace["ops"]
                                if o[0] in OTHER_OPS])
    assert read(SimpleNamespace(**{**vars(ctx), "trace": bare})) is None
    # a configuration of one stream (another cell's, or the parent's)
    other = {k: v for k, v in CONFIG.items() if k != "hc_mult"}
    assert read(SimpleNamespace(**{**vars(ctx), "config": other})) is None


def tick(seq, rows, steps, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "hc_rows": rows, "hc_steps": steps}


def test_hc_rows_per_step_on_tick_records_written_by_hand():
    read = CELL.reader("hc_rows_per_step")
    ctx = SimpleNamespace(
        w0=50.0, w1=150.0, wall_minus_mono=0.0, config=CONFIG, info={},
        streams=[stream(100, 90.0, 300), stream(200, 95.0, 300)], ticks=[
            tick(1, 4 * 128.0 + 32, 4),
            tick(2, 4 * 127.0, 4), tick(2, 4 * 127.0, 4),   # polled twice
            tick(3, 8 * 126.0 + 64, 8),           # a tick that drained two
            tick(4, None, None),                  # a tick that drained none
            tick(5, 9e9, 4, t_wall=10.0)])        # before the window
    assert read(ctx) == pytest.approx(
        (4 * 128 + 32 + 4 * 127 + 8 * 126 + 64) / 16)
    assert ctx.info["hc_rows_per_step"]["streams"] == 2
    ctx.ticks = [tick(1, None, None), {"seq": 2, "t_wall": 100.0}]
    assert read(ctx) is None                  # the parent's records


def test_the_readers_on_tick_records_of_the_cell_s_shape():
    """Six records of a traced run of this cell (my chip run, PR 49,
    seed 2147499301, four steps a block), as `/debug/ticks` gave them:
    four mixed blocks and two decode blocks over 126-127 live streams:
    507-634 positions mixed a tick's block, 4 x 127 and the chunk
    columns of the prompts that rode along."""
    ticks = json.loads((FILES.parent / "recorded_ticks"
                        / "xing29b.rollout.json").read_text())
    assert len(ticks) == 6
    ctx = SimpleNamespace(config=CONFIG, ticks=ticks, wall_minus_mono=0.0,
                          streams=[], info={},
                          w0=ticks[0]["t_wall"] - 1, w1=ticks[-1]["t_wall"] + 1)
    rows = CELL.reader("hc_rows_per_step")(ctx)
    blocks = [t for t in ticks if t["hc_rows"] is not None]
    assert blocks and rows == pytest.approx(
        sum(t["hc_rows"] for t in blocks) / sum(t["hc_steps"] for t in blocks))
    # 128 slots and at most one chunk of 32 a step
    assert 125 <= rows <= 160
    assert [t["hc_rows"] for t in blocks] == [508, 634, 625, 507, 632, 560]
    assert all(t["hc_steps"] == 4 for t in blocks)
    assert all(t["latent_rows"] is not None and t["ssm_rows"] is None
               for t in blocks)
    # the counters of the older metrics the cell is listed in
    assert all(0 < t["experts_touched"] <= 64 for t in blocks)
    lat = CELL.reader("latent_rows_per_step")(ctx)
    assert 10 * 100 <= lat <= 10 * 128 * 2304


# -- the traffic: the file as it is -------------------------------------------

def test_rollout_is_granite_s_file_and_fits_this_configuration():
    assert CELL.traffic_path == \
        Cell(MANIFEST, "granite4h.rollout", ROOT).traffic_path
    p = make_plan(load_traffic(CELL.traffic_path), 2 ** 31 + 49, 45.0,
                  CONFIG["vocab_size"], CONFIG["serve"]["max_seq"])
    assert p.kind == "closed" and len(p.queues) == 128
    assert sorted(q[0].max_tokens for q in p.queues) == \
        [16 * (i + 1) for i in range(128)]
    assert max(len(r.tokens) + r.max_tokens
               for q in p.queues for r in q) <= 2304
    assert max(max(r.tokens) for q in p.queues for r in q) < 131072
    # ten rounds of 2,048 behind the first: 20,480 steps a client
    # planned; 32 finishes come near step 700 at any step time, so the
    # cap of 240 s is not what opens the window (PERF.md has the run)
    assert all(len(q) == 11 for q in p.queues)
    assert (p.lead_finished, p.lead_max_s) == (32, 240.0)


# -- the manifest's entries and the configuration file ------------------------

def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    layer = "models (models/common.py)"
    for name, unit, source, better, moves in (
            ("hc_share", "%", "device_trace", "lower", "tpot_p50_ms"),
            ("hc_roofline", "%", "device_trace", "higher", "tpot_p50_ms"),
            ("hc_rows_per_step", "rows", "program_counter", "higher",
             "out_tok_s")):
        assert by[name] == {"name": name, "unit": unit, "better": better,
                            "source": source, "layer": layer, "moves": moves,
                            "workloads": ["xing29b.rollout"]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("hc_share")
    assert names[at:at + 3] == ["hc_share", "hc_roofline", "hc_rows_per_step"]
    assert names[at - 1] == "latent_rows_per_step"   # appended, not put in
    assert layer in {m["layer"] for m in MANIFEST["per_layer"][:at]}
    # what this PR touches of the accepted benchmark: a metric that read
    # null in an accepted cell is given a list, and the cell is appended
    # to the lists of the layers it runs (the latent read is a third of
    # its busy time, the experts another third)
    older = ["mixed_block_ms_p50", "experts_touched_share",
             "expert_rows_skew", "latent_attn_share", "latent_attn_roofline",
             "latent_rows_per_step"]
    for name in older:
        assert by[name]["workloads"][-1] == "xing29b.rollout"
        assert "xing29b.rollout" not in by[name]["workloads"][:-1]
    assert by["mixed_block_ms_p50"]["workloads"][:-1] == SIX
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[:6] == SIX and cells.index("xing29b.rollout") == 6
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"])
    assert [c["name"] for c in MANIFEST["configs"]].index(cfg["name"]) == 6
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == CONFIG["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    cell = MANIFEST["workloads"][6]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        ("xing29b.rollout", "xing4.0-29b-a4b", "rollout", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    # every per-layer metric without a `workloads` list is the cell's too
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    mine = {m["name"] for m in CELL.per_layer}
    assert unlisted <= mine
    assert mine - unlisted == {"hc_share", "hc_roofline", "hc_rows_per_step",
                               *older}
    assert CONFIG["kernels_must_hold"] == ["latent_win"]
    assert CONFIG["dense_fallback_allowed"] is False
    assert {m["name"] for m in CELL.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}


def test_the_file_holds_every_published_key_and_its_bytes():
    pin = json.loads((ROOT / "servebench/pins/xing4.0-29b-a4b.json")
                     .read_text())
    cat = pin["published"]
    assert len(cat) == 38 + 1           # the source's 38 keys and head_dim
    for key, value in cat.items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert "head_dim" in CONFIG["assumed"] and "head_dim" in pin["note"]
    assert CONFIG["num_hidden_layers"] == 10 >= 4
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["serve"] == {
        "quant": "int8", "kv_quant": "none", "max_batch": 128,
        "max_seq": 2304, "page_size": 16, "decode_steps_per_tick": 4}
    assert "edge_quiet_ms" in CONFIG["serve_why"]
    assert (CONFIG["hc_mult"], CONFIG["hc_sinkhorn_iters"], CONFIG["hc_eps"],
            CONFIG["mhc_h_res_clamp_min"], CONFIG["mhc_h_res_clamp_max"]) \
        == (4, 20, 1e-6, -30, 30)
    model = CONFIG["model"]
    assert (model["hc_mult"], model["hc_sinkhorn_iters"], model["hc_eps"],
            model["hc_clamp_min"], model["hc_clamp_max"]) == \
        (4, 20, 1e-6, -30, 30)
    assert model["rope_scaling"] == CONFIG["rope_scaling"]
    assert model["num_experts"] == CONFIG["n_routed_experts"] == 64
    assert model["first_k_dense"] == CONFIG["first_k_dense_replace"] == 2
    # ISSUE 49's arithmetic, a byte a code: attention, a dense layer, an
    # expert layer, the mixing; then the head, the embedding, the pool
    attn = peaks.attention_params(CONFIG)
    dense, expert = 3 * 3584 * 9216, 3 * 3584 * 1024
    mixing = 2 * (14336 * 24 + 24 + 3)
    assert (attn, dense, 65 * expert, mixing) == \
        (28_409_856, 99_090_432, 715_653_120, 688_182)
    codes = 2 * (attn + dense) + 8 * (attn + 65 * expert)
    assert codes == 6_207_504_384                       # 6.21 GB
    head = 131072 * 3584
    floats = 2 * (head + 8 * 3584 * 64 + 10 * mixing)   # bf16
    weights = codes + head + floats
    assert weights == pytest.approx(7.63e9, rel=2e-3)   # 7.6 GB
    assert 128 * 2304 * 10 * 1280 == 3_774_873_600      # 3.77 GB of lanes
    assert 128 * 2304 * 10 * 1152 == 3_397_386_240      # 3.40 GB of values
    assert 128 * 2304 // 16 == 18_432                   # pages
    assert (weights + 3_774_873_600) / 16e9 == pytest.approx(0.71, abs=0.01)
    assert set(CONFIG["assumed"]) >= {
        "hc_fan_out_and_fold", "hc_columns", "hc_eps", "hc_sinkhorn_order",
        "hc_per_sublayer", "hc_post_factor", "hc_seeded", "rope_scaling",
        "rope_interleave", "num_nextn_predict_layers", "expert_split",
        "torch_dtype", "pool_lanes", "head_dim"}
    assert "pipeline stages" in CONFIG["deployment"]
    assert CONFIG["reference"] == "xing_f32"
    assert 0 < CONFIG["reference_tolerance"] < 1
    assert "control.py" in CONFIG["reference_tolerance_why"]


# -- a toy of the family through the harness, from files alone ----------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, `tinyxing.rollout`, made by adding
    files and entries (tests/servebench/files/ holds the toy's
    configuration and traffic; the reference is the benchmark's own)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    for sub, name in (("configs", "tiny-xing.json"),
                      ("traffic", "tinyrollout.json")):
        shutil.copy(FILES / sub / name, root / "servebench" / sub / name)
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-xing", "source": "tests only",
                         "file": "servebench/configs/tiny-xing.json",
                         "reduced": [], "why": "a toy for the CPU"})
    m["workloads"].append({"name": "tinyxing.rollout", "config": "tiny-xing",
                           "traffic": "tinyrollout", "chips": 1,
                           "why": "closed loop on a toy"})
    for e in m["per_layer"]:
        if e["name"].startswith("hc_"):
            e["workloads"].append("tinyxing.rollout")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_a_toy_of_the_family_runs_from_added_files_alone(checkout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"),
               JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, str(checkout / "servebench" / "run.py"),
         "--workload", "tinyxing.rollout", "--seed", str(2 ** 31 + 49),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = [json.loads(ln) for ln in r.stdout.splitlines()
                 if ln.strip()][-2:]
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    ref = info["refcheck"]
    assert ref["ok"] and ref["max_err"] < 1e-4
    assert ref["reference"] == "xing_f32"
    # the counter reached the line: four slots and one chunk of 32 at
    # most a step; the device's metrics did not (a rehearsal prints none)
    rows = out["metrics"]["hc_rows_per_step"]["value"]
    assert 1 <= rows <= 4 + 32
    assert "hc_share" not in out["metrics"]
    assert "mixed_block_ms_p50" not in out["metrics"]
    assert info["hc_rows_per_step"]["counted"] == rows
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    blocks = [t for t in ticks if t["hc_rows"] is not None]
    assert blocks and all(t["hc_steps"] % 2 == 0 for t in blocks)
    assert all(t["latent_rows"] is not None for t in blocks)


def test_a_program_without_the_family_refuses_the_file_by_name():
    """What the parent of PR 49 does with this cell: the file's "model"
    group names fields its ModelConfig lacks, and
    servebench/launcher.py:model_fields says which before anything is
    built (the launcher exits at once; the chip run is in PERF.md)."""
    import dataclasses
    from unittest import mock

    from butterfly_tpu.core import config as core
    from servebench.launcher import model_fields
    older = dataclasses.make_dataclass("ModelConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(core.ModelConfig)
        if not f.name.startswith(("hc_", "rope_scaling"))])
    with mock.patch.object(core, "ModelConfig", older):
        with pytest.raises(ValueError, match="'rope_scaling' is no field|"
                                             "'hc_mult' is no field"):
            model_fields(CONFIG)
    assert model_fields(CONFIG)["hc_mult"] == 4
