"""What PR 52 adds to the benchmark, on records written out by hand: the
least time of the path by which a lightning indexer selects rows of a
latent cache, from the file's PUBLISHED keys (`servebench/dsa_peaks.py`),
how a trace tells that path's operations, its three readers, the
configuration file (one chip's share of a deployment: four keys cut),
its pin and the entries in the manifest; `servebench/peaks.py`'s own
count of the new file against a hand count that states what it leaves
out; and a toy of the family through the harness on the CPU (a
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
sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import dsa_peaks, latent_peaks, peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELL = Cell(MANIFEST, "glm5-ep16.think", ROOT)
CONFIG = CELL.config
V5E = "TPU v5 lite"
FILES = Path(__file__).resolve().parent / "files"
DSA = Path(__file__).resolve().parent / "files_dsa"
SEVEN = ["mistral7b.batch", "mistral7b-bf16-tp4.batch",
         "smallthinker21b.batch", "keye30b.think", "granite4h.rollout",
         "joyai48b.longthink", "xing29b.rollout"]
CUT = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
       "vocab_size"]


# -- the least time, worked by hand at the cell's sizes ----------------------

def test_sizes_of_an_index_key_a_row_and_an_indexer():
    assert dsa_peaks.is_dsa(CONFIG)
    assert dsa_peaks.index_key_bytes(CONFIG) == 128 * 2 == 256
    assert dsa_peaks.row_bytes(CONFIG) == (512 + 64) * 2 == 1152
    # queries from the QUERY LATENT (2,048), key and weights from the
    # hidden state: 9.37 M parameters, 18.7 MB in bf16
    assert dsa_peaks.indexer_params(CONFIG) == 2048 * 32 * 128 \
        + 6144 * 128 + 6144 * 32 == 9_371_648
    assert dsa_peaks.absorbed_params(CONFIG) == 512 * 64 * (192 + 256) \
        == 14_680_064
    assert dsa_peaks.index_score_flops(CONFIG) == 2 * 32 * 128
    assert dsa_peaks.row_flops(CONFIG) == 2 * 64 * (2 * 512 + 64)
    assert dsa_peaks.rows_selected(CONFIG, 500) == 500
    assert dsa_peaks.rows_selected(CONFIG, 7000) == 2048
    for other in ("joyai48b.longthink", "keye30b.think", "mistral7b.batch"):
        assert not dsa_peaks.is_dsa(Cell(MANIFEST, other, ROOT).config)


def test_the_counts_read_only_public_names_of_the_benchmark():
    """A `benchmark` PR that renames a private helper of peaks.py must
    not break this cell's roofline alone: the expansions' byte a weight
    is reckoned here, int8 codes a byte, bf16 two."""
    import inspect
    import re
    assert not re.search(r"\bpeaks\._", inspect.getsource(dsa_peaks))
    bf16 = dict(CONFIG, serve=dict(CONFIG["serve"], quant="none"))
    parts = [dsa_peaks.dsa_least_seconds(c, V5E, 1, 1, [500])["parts"]
             for c in (CONFIG, bf16)]
    assert parts[1]["expansions"] == 2 * parts[0]["expansions"]
    assert parts[1]["indexers"] == parts[0]["indexers"]


@pytest.mark.parametrize("context, rows, by", [
    (500, 500, 615_464_960), (3100, 2048, 1_477_476_352),
    (7000, 2048, 1_828_913_152)])
def test_the_least_time_of_a_step_s_selection_by_hand(context, rows, by):
    """11 layers, 32 streams of one context: every live position's index
    key at 256 B, min(context, 2,048) latent rows a stream at 1,152 B,
    the indexer's 18.7 MB and the two expansions' 14.7 MB of codes once
    a layer. At 3,100: 279 MB of index keys, 830 MB of rows, 206 MB of
    indexers, 161 MB of expansions: 1.48 GB, 1.80 ms of 819 GB/s; past
    2,048 only the index keys grow."""
    got = dsa_peaks.dsa_least_seconds(CONFIG, V5E, 1, 1, [context] * 32)
    assert got["parts"] == {
        "index_keys": 11 * 32 * context * 256,
        "rows": 11 * 32 * rows * 1152,
        "indexers": 11 * 9_371_648 * 2,
        "expansions": 11 * 14_680_064}
    assert got["bytes"] == sum(got["parts"].values()) == by
    assert got["flops"] == 11 * (
        32 * context * 8192 + 32 * rows * 139_264
        + 2 * 32 * (9_371_648 + 14_680_064))
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(by / 819e9)
    assert (got["live_tokens"], got["selected_tokens"]) == \
        (32 * context, 32 * rows)
    four = dsa_peaks.dsa_least_seconds(CONFIG, V5E, 1, 4, [context] * 32)
    assert four["least_s"] == pytest.approx(4 * got["least_s"])


def test_mixed_contexts_are_taken_one_by_one():
    got = dsa_peaks.dsa_least_seconds(CONFIG, V5E, 1, 1, [100, 5000])
    assert got["parts"]["rows"] == 11 * (100 + 2048) * 1152
    assert got["parts"]["index_keys"] == 11 * 5100 * 256


def test_peaks_py_counts_the_new_file_by_hand_and_what_it_leaves_out():
    """`servebench/peaks.py` reads this file's latent keys, its ONE
    leading dense layer of 12,288, ten layers of E = 16 experts of 2,048
    (`n_routed_experts` as held) with a shared one and a router of
    hidden x 16, and a head of 19,360 rows. It knows no `index_topk`
    (it reads `sa_config`): it counts EVERY live latent row and no index
    key, no indexer; and at E = 16, k = 8 it expects all 16 touched,
    where even routing over 256 touches 16 x (1 - (248/256)^32) = 10.2
    (what the program streams today: every held expert). PERF.md,
    section 7, has what that does to `block_roofline`."""
    attn = peaks.attention_params(CONFIG)
    assert attn == 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 \
        + 512 * 64 * 448 + 64 * 256 * 6144 == 165_019_648
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert (dense, expert) == (226_492_416, 37_748_736)
    assert peaks.num_experts(CONFIG) == 16
    assert peaks.cached_row_bytes(CONFIG) == 1152
    assert latent_peaks.is_latent(CONFIG) and "sa_config" not in CONFIG
    head = 19360 * 6144
    # 32 rows of 8 over "16": 16 x (1 - (1/2)^32), all of them
    assert peaks.streamed_params(CONFIG, 32) == pytest.approx(
        11 * attn + dense + 10 * (6144 * 16 + 17 * expert) + head, rel=1e-6)
    # a token is MULTIPLIED by 8 experts and the shared one here, where
    # 8 x 16 / 256 = 0.5 of its chosen are held
    assert peaks.matmul_params(CONFIG) == 11 * attn + dense \
        + 10 * (6144 * 16 + 9 * expert) + head
    parts, fl = peaks.step_parts(CONFIG, [3750] * 32)
    assert parts["weights"] == pytest.approx(8.58e9, rel=1e-3)
    # every live row, not min(context, 2,048); no index key
    assert parts["rows"] == 32 * 11 * 3750 * 1152 == 1_520_640_000
    assert parts["index_keys"] == parts["state"] == 0
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, [3750] * 32)
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(12.33e-3, rel=2e-3)
    # the model's own count of the same step's attention side: 0.2 GB
    # less in rows, 0.3 + 0.2 GB more in index keys and indexers
    mine = dsa_peaks.dsa_least_seconds(CONFIG, V5E, 1, 1, [3750] * 32)
    rows = mine["parts"]["rows"] + mine["parts"]["index_keys"] \
        + mine["parts"]["indexers"]
    assert rows - parts["rows"] == pytest.approx(-0.15e9, abs=0.05e9)


# -- the path's operations in a trace ----------------------------------------

#: the selecting path as a traced run of the cell named it (my chip
#: run, PR 52, seed 2147484001: 91 of the capture's 760 operations,
#: 1.459 of 2.962 s busy): the Mosaic call in the expert layers' scan and
#: in the dense layer's, the table's index keys as one view (S x mp
#: pages), the sorts of a decode row's and a chunk's scores, a chunk's
#: index scores, the scores and the selection over [S, M], the tie's
#: running count and its relayouts, the call's flat [S x M] block, the
#: index queries, a chunk's pages of latent rows and of index keys and
#: its view of them, the indexer's query weights relaid, the table flat
DSA_OPS = [
    "_latent_select_attention.27___bf16_32_64_512__2_1_0:T_8_128__2_1",
    "_latent_select_attention.26___bf16_32_64_512__2_1_0:T_8_128__2_1",
    "_fusion.1264___bf16_14336_16_128__2_1_0:T_8_128__2_1_S_1___fusio",
    "_sort.68____f32_32_1_7168__2_0_1:T_8_128_S_1____s32_32_1_7168__2",
    "_sort.67____f32_1_32_7168__2_1_0:T_8_128_S_1____s32_1_32_7168__2",
    "_fusion.1260____f32_64_32__0_1:T_8_128_S_1____f32_1_64_32_7168__",
    "_fusion.1265___f32_32_7168__1_0:T_8_128_S_1___fusion_bf16_32_716",
    "_reduce-window.117___s32_32_1_56_128__3_2_1_0:T_8_128_S_1___redu",
    "_reduce-window.115___s32_1_32_56_128__3_2_1_0:T_8_128_S_1___redu",
    "_fusion.1269___f32_229376__0:T_1024_S_1___fusion_f32_229376__0:T",
    "_fusion.1253___f32_32_7168__1_0:T_8_128_S_1___fusion_bf16_7168_1",
    "_fusion.1249___f32_64_1_32_128__3_0_2_1:T_8_128_S_1___fusion_bf1",
    "_fusion.1228___bf16_448_16_640__2_1_0:T_8_128__2_1_S_1___fusion_",
    "_fusion.1246___bf16_7168_640__1_0:T_8_128__2_1_S_1___fusion_bf16",
    "_fusion.1247___bf16_448_16_128__2_1_0:T_8_128__2_1_S_1___fusion_",
    "_copy.335___bf16_11_2048_32_128__3_1_2_0:T_8_128__2_1___copy_bf1",
    "_copy.569___s32_4_8_56_128__3_2_1_0:T_8_128_S_1___copy_s32_4_8_5",
    "_fusion.1259___pred_32_7168__1_0:T_8_128__4_1_S_1___fusion_pred_",
    "_reshape.3903___s32_14336__0:T_1024_S_1___reshape_s32_32_448_1__",
]

#: the experts' three products (37 % of the same capture's busy time),
#: the latent projections, the absorbed queries and the expansions, the
#: dense layer, the head, the window's writer, the weights' relayouts,
#: parts of the path the patterns let go (the scores' sum over heads
#: whose operand's shape the name cuts, the index key's and the head
#: weights' projections: under 1 % of busy together) and the UNSELECTED
#: read of a latent model without an indexer: none is told as the path
OTHER_OPS = [
    "_latent_attention.12___bf16_96_32_512__2_1_0:T_8_128__2_1__",
    "_latent_attention.3___bf16_32_64_512__2_1_0:T_8_128__2_1__",
    "_fusion.1278___bf16_64_6144__1_0:T_8_128__2_1_S_1___fusion_s8_10",
    "_fusion.1276___bf16_64_16_2048__2_0_1:T_8_128__2_1_S_1___fusion_",
    "_fusion.1277___bf16_64_16_2048__2_0_1:T_8_128__2_1_S_1___fusion_",
    "_fusion.1272____f32_64__0:T_128_S_1____bf16_64_1_6144__2_0_1:T_8",
    "_fusion.1262___bf16_32_1_64_512__3_2_0_1:T_8_128__2_1_S_1___fusi",
    "_fusion.1219___bf16_64_1_64_256__3_0_2_1:T_8_128__2_1_S_1___fusi",
    "_fusion.1217____f32_64__0:T_128_S_1____bf16_64_2048__1_0:T_8_128",
    "_copy.332___s8_11_2048_64_256__3_1_2_0:T_8_128__4_1___copy_s8_11",
    "_fusion.1283___bf16_64_1_6144__2_0_1:T_8_128__2_1_S_1___fusion_b",
    "_convolution_multiply_fusion.5___bf16_32_19360__1_0:T_8_128__2_1",
    "_fusion.1261___f32_64_32__0_1:T_8_128_S_1___fusion_f32_1_64_32_7",
    "_fusion.1271___bf16_64_64_256__2_0_1:T_8_128__2_1_S_1___fusion_s",
    "_fusion.1225___bf16_64_512_64__1_2_0:T_8_128__2_1_S_1___fusion_b",
    "_copy_bitcast_fusion.7___bf16_64_1_64_512__3_2_0_1:T_8_128__2_1_",
    "_fusion.1174___bf16_64_12288__1_0:T_8_128__2_1_S_1___fusion_s8_1",
    "_stage_window.27____bf16_11_32_1_256_640__4_3_2_1_0:T_8_128__2_1",
    "_bitcast_multiply_fusion.50___bf16_64_1_576__2_0_1:T_8_128__2_1_",
    "_fusion.1238____f32_64__0:T_128_S_1____f32_64_128__1_0:T_8_128_S",
    "_fusion.1248___f32_64_32__0_1:T_8_128_S_1___fusion_bf16_11_6144_",
    "_fusion.1268___f32_32_256__1_0:T_8_128_S_1___fusion_bf16_11_32_1",
    "_fusion.39___bf16_11_14337_1_16_640__4_3_2_1_0:T_8_128__2_1___fu",
    "_sort.69____f32_64_1_256__2_0_1:T_8_128____s32_64_1_256__2_0_1:T",
]


@pytest.mark.parametrize("name", DSA_OPS)
def test_the_path_is_told_by_the_call_s_name_and_by_shapes(name):
    assert dsa_peaks.is_dsa_op(name, dsa_peaks.dsa_patterns(CONFIG)), name


@pytest.mark.parametrize("name", OTHER_OPS)
def test_another_operation_is_left_out(name):
    assert not dsa_peaks.is_dsa_op(name, dsa_peaks.dsa_patterns(CONFIG)), name


def test_the_patterns_are_made_from_the_file_s_sizes():
    pats = dsa_peaks.dsa_patterns(CONFIG)
    assert pats["call"].search(DSA_OPS[0]) and pats["call"].search(DSA_OPS[1])
    assert not any(pats["call"].search(n) for n in DSA_OPS[2:] + OTHER_OPS)
    # the plain latent read's pattern does not take the selecting call,
    # nor this one the plain read
    plain = latent_peaks.latent_patterns(CONFIG)
    assert plain.search(OTHER_OPS[1]) and not plain.search(DSA_OPS[1])
    assert not plain.search(DSA_OPS[0])
    toy = json.loads((DSA / "configs" / "tiny-glm5.json").read_text())
    small = dsa_peaks.dsa_patterns(toy)
    assert small["call"].search("_latent_select_attention.2___f32_4_4_32__2")
    assert not small["call"].search(DSA_OPS[0])
    assert small["shapes"].search("_fusion.3___f32_4_1_128__2_1_0")
    assert not small["shapes"].search("_fusion.7___bf16_64_6144__1_0")


# -- the three readers --------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


def traced_ctx():
    """A capture of 2.0 s: seven runs of the mixed block (the first cut
    by the capture's start, the last ending with it) and 0.5 s in the
    path's operations, 0.3 of them in the call."""
    ops = [[DSA_OPS[0], 0.3, 600], [DSA_OPS[2], 0.1, 7200],
           [DSA_OPS[3], 0.08, 200], [DSA_OPS[11], 0.02, 7200],
           [OTHER_OPS[3], 0.7, 100], [OTHER_OPS[6], 0.3, 100],
           [OTHER_OPS[2], 0.05, 20]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_mixed_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    streams = [stream(1000, 0.0, 30), stream(3000, 0.0, 30),
               stream(64, 0.0, 300), stream(125, 5.0, 10),
               stream(90, 0.0, 5, end=0.6)]
    return SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                           device={"kind": V5E}, streams=streams,
                           trace_at=2.95, info={})


def test_dsa_share_on_a_trace_written_by_hand():
    ctx = traced_ctx()
    assert CELL.reader("dsa_share")(ctx) == pytest.approx(100 * 0.5 / 1.6)
    assert ctx.info["dsa_share"] == {"path_s": pytest.approx(0.5),
                                     "call_s": pytest.approx(0.3)}


def test_dsa_roofline_on_a_trace_written_by_hand():
    """Three streams generate at the trace's middle (contexts 1,030,
    3,030 and 94); the path took 0.5 of the 1.9 s of block runs, so 0.3
    x 0.5 / 1.9 of a whole block of four steps."""
    ctx = traced_ctx()
    least = dsa_peaks.dsa_least_seconds(CONFIG, V5E, 1, 4, [1030, 3030, 94])
    assert least["parts"]["rows"] == 11 * (1030 + 2048 + 94) * 1152
    got = CELL.reader("dsa_roofline")(ctx)
    assert got == pytest.approx(100 * least["least_s"] / (0.3 * 0.5 / 1.9))
    assert 0 < got < 100
    assert ctx.info["dsa_roofline"]["streams"] == 3


@pytest.mark.parametrize("metric", ["dsa_share", "dsa_roofline"])
def test_nothing_to_read_is_none_and_never_raises(metric):
    read = CELL.reader(metric)
    ctx = traced_ctx()
    assert read(SimpleNamespace(**{**vars(ctx), "trace": {}})) is None
    assert read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    bare = dict(ctx.trace, ops=[o for o in ctx.trace["ops"]
                                if o[0] in OTHER_OPS])
    assert read(SimpleNamespace(**{**vars(ctx), "trace": bare})) is None
    # a configuration without the pair (another cell's)
    for cell in ("joyai48b.longthink", "keye30b.think"):
        other = Cell(MANIFEST, cell, ROOT).config
        assert read(SimpleNamespace(**{**vars(ctx), "config": other})) is None


def tick(seq, local, routed, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "expert_rows_local": local,
            "expert_rows_routed": routed}


def test_experts_local_share_on_tick_records_written_by_hand():
    read = CELL.reader("experts_local_share")
    ctx = SimpleNamespace(
        w0=50.0, w1=150.0, wall_minus_mono=0.0, config=CONFIG, info={},
        streams=[], ticks=[
            tick(1, 60.0, 1024.0),
            tick(2, 70.0, 1024.0), tick(2, 70.0, 1024.0),   # polled twice
            tick(3, 0.0, 512.0),                  # none fell on a held one
            tick(4, None, None),                  # a tick that drained none
            tick(5, 9e9, 1024.0, t_wall=10.0)])   # before the window
    assert read(ctx) == pytest.approx(100 * 130 / 2560)
    ctx.ticks = [tick(1, None, None), {"seq": 2, "t_wall": 100.0}]
    assert read(ctx) is None                  # the parent's records


def test_the_readers_on_tick_records_of_the_cell_s_shape():
    """Six records of a traced run of this cell (my chip run, PR 52,
    four steps a block), as `/debug/ticks` gave them."""
    ticks = json.loads((FILES.parent / "recorded_ticks"
                        / "glm5-ep16.think.json").read_text())
    assert len(ticks) == 6
    ctx = SimpleNamespace(config=CONFIG, ticks=ticks, wall_minus_mono=0.0,
                          streams=[], info={},
                          w0=ticks[0]["t_wall"] - 1, w1=ticks[-1]["t_wall"] + 1)
    blocks = [t for t in ticks if t["expert_rows_routed"]]
    assert blocks
    share = CELL.reader("experts_local_share")(ctx)
    assert share == pytest.approx(
        100 * sum(t["expert_rows_local"] for t in blocks)
        / sum(t["expert_rows_routed"] for t in blocks))
    # 16 of 256 held: 6.25 % under even routing
    assert 2 < share < 12
    # a block of four steps routes 32-64 rows x 8 a step
    assert all(4 * 8 * 20 <= t["expert_rows_routed"] <= 4 * 8 * 64
               for t in blocks)
    assert all(t["latent_rows"] is None and t["ssm_rows"] is None
               and t["hc_rows"] is None for t in blocks)
    # the counters of the older metrics the cell is listed in: over the
    # 16 HELD experts; a decode row attends 2,048 of its live positions
    assert all(0 < t["experts_touched"] <= 16 for t in blocks)
    assert 0 < CELL.reader("experts_touched_share")(ctx) <= 100
    assert CELL.reader("expert_rows_skew")(ctx) >= 1
    sel = CELL.reader("kv_selected_share")(ctx)
    assert 2048 / 7168 * 100 <= sel <= 100
    assert all(t["kv_rows_selected"] <= 2048 < 7168 and
               t["kv_rows_moved"] == pytest.approx(t["kv_rows_live"])
               for t in blocks)


# -- the traffic: the file as it is -------------------------------------------

def test_think_is_keye_s_file_and_fits_this_configuration():
    assert CELL.traffic_path == \
        Cell(MANIFEST, "keye30b.think", ROOT).traffic_path
    p = make_plan(load_traffic(CELL.traffic_path), 2 ** 31 + 52, 45.0,
                  CONFIG["vocab_size"], CONFIG["serve"]["max_seq"])
    assert p.kind == "closed" and len(p.queues) == 32
    assert sorted(q[0].max_tokens for q in p.queues) == \
        [96 * (i + 1) for i in range(32)]
    assert max(len(r.tokens) + r.max_tokens
               for q in p.queues for r in q) <= 7168
    # the ids are drawn from the slice of the vocabulary the chip holds
    assert max(max(r.tokens) for q in p.queues for r in q) < 19360
    assert (p.lead_finished, p.lead_max_s) == (16, 240.0)


# -- the manifest's entries, the file and its pin -----------------------------

def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, better, source, layer in (
            ("dsa_share", "lower", "device_trace", "kernels (ops/)"),
            ("dsa_roofline", "higher", "device_trace", "kernels (ops/)"),
            ("experts_local_share", "higher", "program_counter",
             "models (models/common.py)")):
        assert by[name] == {"name": name, "unit": "%", "better": better,
                            "source": source, "layer": layer,
                            "moves": "tpot_p50_ms",
                            "workloads": ["glm5-ep16.think"]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("dsa_share")
    assert names[at:at + 3] == ["dsa_share", "dsa_roofline",
                                "experts_local_share"]
    assert names[at - 1] == "hc_rows_per_step"       # appended, not put in
    # the cell is appended to the lists of the layers it runs and whose
    # readers hold here, and to no list whose count reads `sa_config` or
    # every live row
    older = ["mixed_block_ms_p50", "kv_selected_share",
             "experts_touched_share", "expert_rows_skew"]
    for name in older:
        assert "glm5-ep16.think" in by[name]["workloads"]
        assert by[name]["workloads"].index("glm5-ep16.think") > \
            by[name]["workloads"].index("keye30b.think")
    for name in ("latent_attn_share", "latent_attn_roofline",
                 "latent_rows_per_step", "sparse_attn_share",
                 "sparse_attn_roofline"):
        assert "glm5-ep16.think" not in by[name]["workloads"]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[:7] == SEVEN and cells.index("glm5-ep16.think") == 7
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"])
    assert [c["name"] for c in MANIFEST["configs"]].index(cfg["name"]) == 7
    assert cfg["reduced"] == CUT
    assert cfg["source"] == CONFIG["source"] == \
        "https://huggingface.co/zai-org/GLM-5/blob/main/config.json"
    cell = MANIFEST["workloads"][7]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        ("glm5-ep16.think", "glm-5-ep16", "think", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    mine = {m["name"] for m in CELL.per_layer}
    assert unlisted <= mine
    assert mine - unlisted == {"dsa_share", "dsa_roofline",
                               "experts_local_share", *older}
    assert CONFIG["kernels_must_hold"] == ["latent_select_win"]
    assert CONFIG["dense_fallback_allowed"] is False
    assert {m["name"] for m in CELL.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}


def test_the_file_holds_every_published_key_and_states_its_share():
    pin = json.loads((ROOT / "servebench/pins/glm-5-ep16.json").read_text())
    cat = pin["published"]
    assert len(cat) == 39 + 1       # the source's 39 keys and rope_theta
    for key, value in cat.items():
        if key not in CUT:
            assert CONFIG[key] == value, key
    assert "rope_theta" in CONFIG["assumed"] and "rope_theta" in pin["note"]
    assert CONFIG["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert CONFIG["reduced"] == CUT
    assert CONFIG["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    assert pin["held"] == {k: CONFIG[k] for k in CUT} == {
        "num_hidden_layers": 11, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "vocab_size": 19360}
    # the guide's floors: a whole period (one layer) and four layers at
    # least behind the dense ones, 8 experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - 1 >= 4
    assert CONFIG["n_routed_experts"] >= CONFIG["num_experts_per_tok"] == 8
    assert CONFIG["vocab_size"] * 8 == 154880
    assert CONFIG["serve"] == {
        "quant": "int8", "kv_quant": "none", "max_batch": 32,
        "max_seq": 7168, "page_size": 16, "decode_steps_per_tick": 4}
    model = CONFIG["model"]
    assert (model["num_experts"], model["experts_held"],
            model["experts_first"]) == (256, 16, 0)
    assert model["experts_held"] == CONFIG["n_routed_experts"]
    assert (model["index_heads"], model["index_head_dim"],
            model["index_topk"]) == (CONFIG["index_n_heads"],
                                     CONFIG["index_head_dim"],
                                     CONFIG["index_topk"]) == (32, 128, 2048)
    assert model["first_k_dense"] == CONFIG["first_k_dense_replace"] == 1
    for key in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "routed_scaling_factor",
                "moe_intermediate_size"):
        assert model[key] == CONFIG[key], key
    assert set(CONFIG["assumed"]) >= {
        "num_nextn_predict_layers", "indexer_kernels", "indexer_input",
        "indexer_rope", "rope_theta", "n_shared_experts", "expert_split",
        "pool_lanes", "torch_dtype", "head_dim", "indexer_layers",
        "first_k_dense_replace", "n_routed_experts", "vocab_size"}
    for said in ("16-way expert-parallel", "LEFT OUT", "NOT run",
                 "pipeline stages"):
        assert said in CONFIG["deployment"], said
    assert CONFIG["reference"] == "glm5_f32"
    assert 0 < CONFIG["reference_tolerance"] < 1
    assert "control.py" in CONFIG["reference_tolerance_why"]


def test_a_tiny_configuration_with_all_four_cuts_is_held_to_its_pin():
    """The toy of the family and its pin (tests/servebench/files_dsa/,
    a path of their own as a later PR's directory would be): four keys
    cut, each held, published and listed, and the pin test of the
    harness takes them; a width that differs is still refused."""
    from test_servebench_manifest import TOY, held_to_its_pin
    entry = {"name": "tiny-glm5", "source": "tests only", "reduced": CUT,
             "file": str(DSA / "configs" / "tiny-glm5.json")}
    manifest = dict(TOY, paths=TOY["paths"] + [str(DSA)])
    held_to_its_pin(entry, manifest)
    toy = json.loads((DSA / "configs" / "tiny-glm5.json").read_text())
    assert toy["reduced"] == CUT and set(toy["published"]) == set(CUT)
    with pytest.raises(AssertionError, match="is not the file's"):
        held_to_its_pin(dict(entry, reduced=CUT[:3]), manifest)


# -- a toy of the family through the harness, from files alone ----------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, `tinyglm5.rollout`, made by adding
    files and entries (the toy's configuration; the traffic is the
    tests' own, the reference the benchmark's)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    shutil.copy(DSA / "configs" / "tiny-glm5.json",
                root / "servebench" / "configs" / "tiny-glm5.json")
    shutil.copy(FILES / "traffic" / "tinyrollout.json",
                root / "servebench" / "traffic" / "tinyrollout.json")
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-glm5", "source": "tests only",
                         "file": "servebench/configs/tiny-glm5.json",
                         "reduced": CUT, "why": "a toy for the CPU"})
    m["workloads"].append({"name": "tinyglm5.rollout", "config": "tiny-glm5",
                           "traffic": "tinyrollout", "chips": 1,
                           "why": "closed loop on a toy"})
    for e in m["per_layer"]:
        if "glm5-ep16.think" in e.get("workloads", ()):
            e["workloads"].append("tinyglm5.rollout")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_a_toy_of_the_family_runs_from_added_files_alone(checkout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"),
               JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, str(checkout / "servebench" / "run.py"),
         "--workload", "tinyglm5.rollout", "--seed", str(2 ** 31 + 52),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = [json.loads(ln) for ln in r.stdout.splitlines()
                 if ln.strip()][-2:]
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    ref = info["refcheck"]
    assert ref["ok"] and ref["max_err"] < 1e-4
    assert ref["reference"] == "glm5_f32"
    # the counters reached the line: experts 4-5 of 8 held (25 % under
    # even routing), a top-k of 8 against contexts to 128; the device's
    # metrics did not (a rehearsal prints none)
    got = out["metrics"]
    assert 0 < got["experts_local_share"]["value"] < 60
    assert 8 / 128 * 100 <= got["kv_selected_share"]["value"] < 100
    assert 0 < got["experts_touched_share"]["value"] <= 100
    assert "dsa_share" not in got and "dsa_roofline" not in got
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    blocks = [t for t in ticks if t["expert_rows_routed"]]
    assert blocks and all(t["latent_rows"] is None for t in blocks)
    assert all(t["expert_rows_local"] <= t["expert_rows_routed"]
               for t in blocks)
    assert all(t["experts_touched"] <= 2 for t in blocks
               if t["experts_touched"] is not None)


def test_a_program_without_the_share_refuses_the_file_by_name():
    """What the parent of PR 52 does with this cell: the file's "model"
    group names fields its ModelConfig lacks, and
    servebench/launcher.py:model_fields says which before anything is
    built (the launcher exits at once; the chip run is in PERF.md)."""
    import dataclasses
    from unittest import mock

    from butterfly_tpu.core import config as core
    from servebench.launcher import model_fields
    older = dataclasses.make_dataclass("ModelConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(core.ModelConfig)
        if not f.name.startswith("experts_")])
    with mock.patch.object(core, "ModelConfig", older):
        with pytest.raises(ValueError, match="'experts_held' is no field"):
            model_fields(CONFIG)
    assert model_fields(CONFIG)["experts_held"] == 16
