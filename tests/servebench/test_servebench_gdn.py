"""What PR 56 adds to the benchmark, on records written out by hand: the
least time of a Gated DeltaNet mixer from the file's PUBLISHED keys
(`servebench/gdn_peaks.py`), how a trace tells the mixers' operations,
its three readers, the configuration file (nothing cut), its pin and the
entries in the manifest; what `servebench/peaks.py` reads for the new
file and what it leaves out, by hand; and a toy of the family through the
harness on the CPU (a rehearsal), added from files alone."""
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

from servebench import gdn_peaks, peaks, ssm_peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
CELL = Cell(MANIFEST, "olmohybrid7b.batch", ROOT)
CONFIG = CELL.config
V5E = "TPU v5 lite"
FILES = Path(__file__).resolve().parent / "files"
GDN = Path(__file__).resolve().parent / "files_gdn"
EIGHT = ["mistral7b.batch", "mistral7b-bf16-tp4.batch",
         "smallthinker21b.batch", "keye30b.think", "granite4h.rollout",
         "joyai48b.longthink", "xing29b.rollout", "glm5-ep16.think"]


# -- the least time, worked by hand at the cell's sizes ----------------------

def test_sizes_of_a_mixer_and_of_a_stream_s_state():
    assert gdn_peaks.linear_layers(CONFIG) == 24
    assert [gdn_peaks.is_linear(CONFIG, l) for l in range(8)] == \
        [True, True, True, False] * 2
    assert gdn_peaks.sizes(CONFIG) == {
        "heads": 30, "key": 2880, "value": 5760, "conv": 11520,
        "proj": 17280}
    # q, k, v, z and the output: 6 x hidden^2; a and b: 60 columns
    assert gdn_peaks.proj_params(CONFIG) == 3840 * (17280 + 5760) \
        == 6 * 3840 ** 2 == 88_473_600
    assert gdn_peaks.ab_params(CONFIG) == 3840 * 60 == 230_400
    assert gdn_peaks.heads_state(CONFIG) == 30 * 192 * 96 == 552_960
    assert gdn_peaks.state_values(CONFIG) == 552_960 + 3 * 11520 == 587_520
    # read once and written once, two bytes a value
    assert gdn_peaks.state_bytes(CONFIG) == 587_520 * 4 == 2_350_080
    # decay 1, S k 2, the update 2, S q 2
    assert gdn_peaks.state_flops(CONFIG) == 7 * 552_960
    assert gdn_peaks.weight_bytes(CONFIG) == 88_473_600 + 2 * 230_400
    bf16 = dict(CONFIG, serve=dict(CONFIG["serve"], quant="none"))
    assert gdn_peaks.weight_bytes(bf16) == 2 * (88_473_600 + 230_400)
    # a slot's state, all 24 layers: the deployment's 28,200,960 B
    assert 24 * gdn_peaks.state_values(CONFIG) * 2 == 28_200_960
    for other in EIGHT:
        assert gdn_peaks.linear_layers(Cell(MANIFEST, other, ROOT).config) \
            == 0
    assert ssm_peaks.mamba_layers(CONFIG) == 0


@pytest.mark.parametrize("live, by", [(61, 5_574_942_720),
                                      (64, 5_744_148_480),
                                      (1, 2_190_827_520)])
def test_the_least_time_of_a_step_s_mixers_by_hand(live, by):
    """24 layers: 88.9 MB of weights a layer once, 2.35 MB of state a
    live stream read and written. At 61 streams 2.13 GB + 3.44 GB =
    5.57 GB, 6.8 ms of 819 GB/s; the operations (two a parameter and
    row, seven a state value) are a fifth of that at the bf16 peak."""
    got = gdn_peaks.gdn_least_seconds(CONFIG, V5E, 1, 1, live)
    assert got["bytes"] == 24 * (88_934_400 + live * 2_350_080) == by
    assert got["flops"] == 24 * live * (2 * (88_473_600 + 230_400)
                                        + 3_870_720)
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(by / 819e9)
    four = gdn_peaks.gdn_least_seconds(CONFIG, V5E, 1, 4, live)
    assert four["least_s"] == pytest.approx(4 * got["least_s"])


def test_the_counts_read_only_public_names_of_the_benchmark():
    import inspect
    import re
    assert not re.search(r"\bpeaks\._", inspect.getsource(gdn_peaks))
    assert "import jax" not in inspect.getsource(gdn_peaks)


def test_peaks_py_counts_the_new_file_by_hand_and_what_it_leaves_out():
    """`servebench/peaks.py` (the benchmark's, not edited here) reads
    `layer_types`: "mamba" with the `mamba_*` keys, anything else
    attention. For this file it counts 32 attention layers of 30 KV
    heads: 0.71 GB too few projection bytes (24 mixers of 88.5 M read as
    59.0 M), 3.82 GB of rows that 24 layers do not have, and no state
    (3.44 GB): 11.43 GB where the model moves 11.76, 2.8 % low at 61
    streams of context 170; 2.9 % high at 200, 8.4 % high at 230.
    PERF.md, section 7, has it for the next `benchmark` PR."""
    attn = peaks.attention_params(CONFIG)
    assert attn == 3840 * 30 * 128 * 4 == 58_982_400
    assert all(peaks.mixer_params(CONFIG, l) == attn for l in range(32))
    ffn, head = 3 * 3840 * 11008, 100352 * 3840
    assert peaks.streamed_params(CONFIG, 61) == 32 * (attn + ffn) + head \
        == 6_330_777_600
    assert peaks.cached_row_bytes(CONFIG) == 2 * 30 * 128 * 2 == 15_360
    assert all(peaks.rows_read(CONFIG, l, 170) == 170 for l in range(32))

    def both(context):
        parts, _ = peaks.step_parts(CONFIG, [context] * 61)
        assert parts["state"] == parts["index_keys"] == 0
        assert parts["rows"] == 32 * 61 * context * 15_360
        read = parts["weights"] + parts["rows"]
        # the model: 24 mixers and 8 attention layers, the feed-forwards
        # and the head; 8 layers of rows; 24 layers of state
        true = 24 * gdn_peaks.weight_bytes(CONFIG) + 8 * attn \
            + 32 * ffn + head + 8 * 61 * context * 15_360 \
            + 24 * 61 * gdn_peaks.state_bytes(CONFIG)
        return read, true

    read, true = both(170)
    assert read == pytest.approx(11.428e9, rel=1e-3)
    assert true == pytest.approx(11.764e9, rel=1e-3)
    assert true - read == pytest.approx(
        0.708e9 + 0.011e9 + 3.440e9 - 3.823e9, rel=2e-2)
    assert read / true == pytest.approx(0.972, abs=2e-3)
    for context, ratio in ((200, 1.029), (230, 1.084)):
        read, true = both(context)
        assert read / true == pytest.approx(ratio, abs=3e-3)
    # so block_roofline, which reads 40-70 % in every cell, cannot pass
    # 105 % for this error
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, [170] * 61)
    assert got["bound"] == "memory"
    assert got["least_s"] == pytest.approx(13.95e-3, rel=2e-3)


#: tests/servebench/test_servebench_peaks.py's own five sets of live
#: streams' contexts
CONTEXTS = [[300] * 32, [1164] * 128, [100, 2048, 2049, 4096, 4097, 6000],
            [33 + 211 * i for i in range(32)], []]


@pytest.mark.parametrize("contexts", CONTEXTS, ids=lambda c: f"n{len(c)}")
def test_the_whole_step_of_the_new_file_is_the_sum_of_its_parts(contexts):
    """What test_servebench_peaks.py holds every accepted file to, for
    this file under what peaks.py READS it as (tests/conftest.py takes
    that test's five cases of this file out: its branch for a file with
    `layer_types` is granite's own): the whole is the sum of the parts,
    weights as `weight_bytes` gives them, every one of 32 layers read as
    attention over every live row, no state, no index key; four steps
    are four times one, twice the chips half the time."""
    live = len(contexts)
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, contexts)
    parts = got["parts"]
    assert set(parts) == {"weights", "rows", "index_keys", "state"}
    assert got["bytes"] == parts["weights"] + parts["rows"] \
        + parts["index_keys"] + parts["state"]
    assert parts["weights"] == peaks.weight_bytes(CONFIG, live) \
        == 6_330_777_600
    assert parts["state"] == parts["index_keys"] == 0.0
    assert parts["rows"] == 32 * sum(contexts) * 15_360
    assert not any(ssm_peaks.is_mamba(CONFIG, l) for l in range(32))
    block = peaks.block_least_seconds(CONFIG, V5E, 2, 4, contexts)
    assert block["bytes"] == 4 * got["bytes"]
    assert block["memory_s"] == pytest.approx(2 * got["memory_s"])
    # the mixers' own count is a sum of the same per-layer functions a
    # `benchmark` PR would hand peaks.py
    mine = gdn_peaks.gdn_least_seconds(CONFIG, V5E, 1, 1, live)
    assert mine["bytes"] == 24 * (gdn_peaks.weight_bytes(CONFIG)
                                  + live * gdn_peaks.state_bytes(CONFIG))


# -- the mixers' operations in a trace ----------------------------------------

#: the mixers as a traced run of the cell named them (my chip run, PR 56,
#: seed 2147484822: 1.571 of 2.918 s busy): the update of a layer's state
#: in place and the reduction over it (alpha S k and alpha S q at once),
#: a chunk's products with its slot's state and the sum into it, the wide
#: in-projection, the conv over 11,520 channels and its tail, a head's
#: normalised keys and normed readout, the gate's input, and a chunk's
#: solve a head: [30, 32, 32], [30, 32, 96], [30, 32, 192]
GDN_OPS = [
    "_fusion.3318___bf16_24_64_15_96_384__4_3_2_1_0:T_8_128__2_1___fu",
    "_fusion.3382____f32_64_15_384__2_1_0:T_8_128_S_1____f32_64_15_38",
    "_multiply_reduce_fusion.183____f32_32_15_384__2_1_0:T_8_128_S_1_",
    "_fusion.3379___f32_15_96_384__2_1_0:T_8_128_S_1___fusion_f32_32_",
    "_bitcast_multiply_fusion.90___bf16_96_1_17280__2_0_1:T_8_128__2_",
    "_fusion.3320___bf16_192_11520__1_0:T_8_128__2_1_S_1___fusion_bf1",
    "_bitcast_dynamic-update-slice_fusion.18___bf16_24_3_64_11520__3_",
    "_divide_multiply_fusion.37___f32_64_1_11520__2_0_1:T_8_128_S_1__",
    "_reduce.2112___f32_64_30__1_0:T_8_128_S_1___reduce_f32_64_30_96_",
    "_fusion.3311___f32_64_1_30_192__3_2_0_1:T_8_128_S_1___fusion_f32",
    "_reshape.8226___f32_96_1_5760__2_0_1:T_8_128_S_1___reshape_f32_9",
    "_fusion.3376___f32_30_32_192__2_1_0:T_8_128_S_1___fusion_f32_30_",
    "_multiply_bitcast_fusion.36___f32_30_32_96__2_0_1:T_8_128_S_1___",
    "_convolution_multiply_fusion.29___f32_30_32_32__2_1_0:T_8_128_S_",
]

#: the same capture's other operations: the paged read, the head, the
#: feed-forward's products, the window's writer, the pool, the attention
#: layers' projections and a chunk's scores over its 400 cached
#: positions, the out-projection's result (told from no other layer's)
#: and the a and b projections' weights (60 columns: let go)
OTHER_OPS = [
    "_paged_attention.97___bf16_64_30_128__2_1_0:T_8_128__2_1_S_1___c",
    "_convolution_multiply_fusion.26___bf16_64_100352__1_0:T_8_128__2",
    "_fusion.3393___bf16_96_11008__1_0:T_8_128__2_1_S_1___fusion_s8_3",
    "_multiply_reduce_fusion.186____f32_96__0:T_128_S_1____bf16_96_38",
    "_stage_window.1____bf16_8_64_30_256_128__4_3_2_1_0:T_8_128__2_1_",
    "_fusion.46____bf16_8_1601_30_16_128__4_3_2_1_0:T_8_128__2_1____b",
    "_fusion.3054___bf16_96_30_128__2_0_1:T_8_128__2_1_S_1___fusion_s",
    "_fusion.3314___bf16_96_1_3840__2_0_1:T_8_128__2_1_S_1___fusion_b",
    "_fusion.3060___f32_30_32_400__2_1_0:T_8_128_S_1___fusion_bf16_40",
    "_fusion.3075____f32_30_32__1_0:T_8_128_S_1____f32_30_32_400__2_1",
    "_copy.2993___bf16_24_3840_60__2_1_0:T_8_128__2_1_S_1___copy_bf16",
    "_fusion.3050___bf16_96_3840__1_0:T_8_128__2_1_S_1___fusion_bf16_",
    "_fusion.1264___bf16_14336_16_128__2_1_0:T_8_128__2_1_S_1___fusio",
    # an attention layer's results heads first, at a mixed step of as
    # many rows as dk (64 + 32 = 96) and a chunk of 32: the shape goes
    # on behind dk, or behind c
    "_fusion.3061___bf16_30_96_128__2_1_0:T_8_128__2_1_S_1___fusion_b",
    "_fusion.3062___f32_30_32_32_128__3_2_1_0:T_8_128_S_1___fusion_f3",
    "_fusion.3063___f32_30_32_96_128__3_2_1_0:T_8_128_S_1___fusion_f3",
]


@pytest.mark.parametrize("name", GDN_OPS)
def test_the_mixers_are_told_by_the_shapes_only_they_produce(name):
    assert gdn_peaks.gdn_patterns(CONFIG).search(name), name


@pytest.mark.parametrize("name", OTHER_OPS)
def test_another_operation_is_left_out(name):
    assert not gdn_peaks.gdn_patterns(CONFIG).search(name), name


def test_the_patterns_are_made_from_the_file_s_sizes():
    toy = json.loads((GDN / "configs" / "tiny-olmo-hybrid.json").read_text())
    small = gdn_peaks.gdn_patterns(toy)
    # q | k | v | z = 384, conv 256, values 128, keys 64; four heads'
    # values in one row of 128 lanes: [.., 1, 16, 128]
    assert small.search("_fusion.3___f32_4_1_384__2_1_0")
    assert small.search("_fusion.9___f32_2_4_1_16_128__4_3_2_1_0")
    # (a toy's widths, 64 to 384, are everyone's: it tells nothing apart)
    assert not small.search("_fusion.7___bf16_96_11008__1_0")
    # Mamba-2's pattern does not take these, nor this one granite's
    granite = Cell(MANIFEST, "granite4h.rollout", ROOT).config
    mamba = ssm_peaks.ssm_patterns(granite)
    assert not any(mamba.search(n) for n in GDN_OPS)
    assert not gdn_peaks.gdn_patterns(CONFIG).search(
        "_ssm_step.3___bf16_9_128_128_64_128__4_3_2_1_0:T_8_128__2_1_")


# -- the three readers --------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


def traced_ctx():
    """A capture of 2.0 s: seven runs of the mixed block (the first cut
    by the capture's start, the last ending with it) and 0.8 s in the
    mixers' operations."""
    ops = [[GDN_OPS[0], 0.3, 600], [GDN_OPS[1], 0.3, 600],
           [GDN_OPS[4], 0.15, 600], [GDN_OPS[13], 0.05, 600],
           [OTHER_OPS[0], 0.5, 800], [OTHER_OPS[2], 0.2, 200],
           [OTHER_OPS[10], 0.05, 600]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_mixed_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    streams = [stream(100, 0.0, 30), stream(120, 0.0, 30),
               stream(64, 0.0, 300), stream(125, 5.0, 10),
               stream(90, 0.0, 5, end=0.6)]
    return SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                           device={"kind": V5E}, streams=streams,
                           trace_at=2.95, info={})


def test_gdn_share_on_a_trace_written_by_hand():
    assert CELL.reader("gdn_share")(traced_ctx()) == \
        pytest.approx(100 * 0.8 / 1.6)


def test_gdn_roofline_on_a_trace_written_by_hand():
    """Three streams generate at the trace's middle; the mixers took 0.8
    of the 1.9 s of block runs, so 0.3 x 0.8 / 1.9 of a whole block of
    four steps."""
    ctx = traced_ctx()
    least = gdn_peaks.gdn_least_seconds(CONFIG, V5E, 1, 4, 3)
    got = CELL.reader("gdn_roofline")(ctx)
    assert got == pytest.approx(100 * least["least_s"] / (0.3 * 0.8 / 1.9))
    assert 0 < got < 100
    assert ctx.info["gdn_roofline"]["streams"] == 3
    assert ctx.info["gdn_roofline"]["path_s"] == pytest.approx(0.3 * 0.8 / 1.9)


def tick(seq, rows, steps, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "ssm_rows": rows,
            "ssm_steps": steps, "state_resets": 0}


def test_gdn_rows_per_step_on_tick_records_written_by_hand():
    read = CELL.reader("gdn_rows_per_step")
    ctx = SimpleNamespace(
        w0=50.0, w1=150.0, wall_minus_mono=0.0, config=CONFIG, info={},
        streams=[], ticks=[
            tick(1, 4 * 64 + 23, 4),
            tick(2, 4 * 60, 4), tick(2, 4 * 60, 4),     # polled twice
            tick(3, None, None),                  # a tick that drained none
            tick(4, 9e9, 4, t_wall=10.0)])        # before the window
    assert read(ctx) == pytest.approx((4 * 64 + 23 + 4 * 60) / 8)
    ctx.ticks = [tick(1, None, None), {"seq": 2, "t_wall": 100.0}]
    assert read(ctx) is None                  # a program without the count
    # granite's tick records hold the same counter: its file has no
    # linear-attention layer, and this reader says nothing there
    granite = Cell(MANIFEST, "granite4h.rollout", ROOT).config
    ctx.ticks = [tick(1, 100, 4)]
    assert read(SimpleNamespace(**{**vars(ctx), "config": granite})) is None
    assert read(ctx) == 25.0


@pytest.mark.parametrize("metric", ["gdn_share", "gdn_roofline"])
def test_nothing_to_read_is_none_and_never_raises(metric):
    read = CELL.reader(metric)
    ctx = traced_ctx()
    assert read(SimpleNamespace(**{**vars(ctx), "trace": {}})) is None
    assert read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    bare = dict(ctx.trace, ops=[o for o in ctx.trace["ops"]
                                if o[0] in OTHER_OPS])
    assert read(SimpleNamespace(**{**vars(ctx), "trace": bare})) is None
    for cell in ("granite4h.rollout", "mistral7b.batch"):
        other = Cell(MANIFEST, cell, ROOT).config
        assert read(SimpleNamespace(**{**vars(ctx), "config": other})) is None


# -- the traffic: the file as it is -------------------------------------------

def test_batch_is_mistral_s_file_and_fits_this_configuration():
    assert CELL.traffic_path == \
        Cell(MANIFEST, "mistral7b.batch", ROOT).traffic_path
    p = make_plan(load_traffic(CELL.traffic_path), 2 ** 31 + 56, 45.0,
                  CONFIG["vocab_size"], CONFIG["serve"]["max_seq"])
    sent = [r for q in p.queues for r in q] if p.queues \
        else [r for _, r in p.schedule]
    assert len(sent) == 4096
    assert max(len(r.tokens) + r.max_tokens for r in sent) <= 128 + 256 \
        < CONFIG["serve"]["max_seq"] == 400
    assert min(len(r.tokens) for r in sent) >= 32
    assert max(max(r.tokens) for r in sent) < 100352


# -- the manifest's entries, the file and its pin -----------------------------

def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, unit, better, source, layer, moves in (
            ("gdn_share", "%", "lower", "device_trace", "kernels (ops/)",
             "tpot_p50_ms"),
            ("gdn_roofline", "%", "higher", "device_trace",
             "kernels (ops/)", "tpot_p50_ms"),
            ("gdn_rows_per_step", "rows", "higher", "program_counter",
             "cache manager (cache/)", "out_tok_s")):
        assert by[name] == {"name": name, "unit": unit, "better": better,
                            "source": source, "layer": layer, "moves": moves,
                            "workloads": ["olmohybrid7b.batch"]}
        assert by[name]["moves"] == by[name.replace("gdn", "ssm")]["moves"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("gdn_share")
    assert names[at:at + 3] == ["gdn_share", "gdn_roofline",
                                "gdn_rows_per_step"]
    assert names[at - 1] == "stall_ticks"            # appended, not put in
    listed = by["mixed_block_ms_p50"]["workloads"]
    assert listed[:8] == EIGHT and listed.index("olmohybrid7b.batch") == 8
    for name in ("ssm_share", "ssm_roofline", "ssm_rows_per_step",
                 "experts_touched_share", "latent_attn_share", "hc_share",
                 "dsa_share", "kv_selected_share", "collective_share"):
        assert "olmohybrid7b.batch" not in by[name]["workloads"]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[:8] == EIGHT and cells.index("olmohybrid7b.batch") == 8
    assert [c["name"] for c in MANIFEST["configs"]].index(
        "olmo-hybrid-7b") == 8
    cfg = MANIFEST["configs"][8]
    assert cfg["reduced"] == [] and cfg["source"] == CONFIG["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    cell = MANIFEST["workloads"][8]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmohybrid7b.batch", "olmo-hybrid-7b", "batch", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    mine = {m["name"] for m in CELL.per_layer}
    assert unlisted <= mine and "block_roofline" in mine
    assert mine - unlisted == {"gdn_share", "gdn_roofline",
                               "gdn_rows_per_step", "mixed_block_ms_p50"}
    assert {m["name"] for m in CELL.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}


def test_the_file_holds_every_published_key_and_cuts_nothing():
    pin = json.loads((ROOT / "servebench/pins/olmo-hybrid-7b.json")
                     .read_text())
    cat = pin["published"]
    assert len(cat) == 20 + 2       # the source's 20 keys, rope_theta, head_dim
    for key, value in cat.items():
        assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    assert CONFIG["rope_theta"] is None and CONFIG["head_dim"] == 128
    assert CONFIG["reduced"] == [] == list(pin["held"])
    assert "published" not in CONFIG
    assert CONFIG["num_hidden_layers"] == 32 and CONFIG["vocab_size"] == 100352
    assert CONFIG["serve"] == {
        "quant": "int8", "kv_quant": "none", "max_batch": 64,
        "max_seq": 400, "page_size": 16, "decode_steps_per_tick": 4}
    model = CONFIG["model"]
    assert model["layer_types"] == (["linear_attention"] * 3
                                    + ["attention"]) * 8
    assert (model["gdn_heads"], model["gdn_key_dim"], model["gdn_value_dim"],
            model["gdn_conv"], model["gdn_neg_eigval"]) == (
        CONFIG["linear_num_value_heads"], CONFIG["linear_key_head_dim"],
        CONFIG["linear_value_head_dim"], CONFIG["linear_conv_kernel_dim"],
        CONFIG["linear_allow_neg_eigval"]) == (30, 96, 192, 4, True)
    assert model["pos_embedding"] == "none" and model["post_norm"] \
        and model["qk_norm_wide"]
    assert set(CONFIG["assumed"]) == {
        "head_dim", "rope_theta", "norms", "mixer", "conv", "state_dtype",
        "state_layout", "layer_types", "torch_dtype"}
    for said in ("WHOLE model", "nothing cut", "28,200,960", "64 slots",
                 "1,600 pages"):
        assert said in CONFIG["deployment"], said
    assert CONFIG["kernels_must_hold"] == ["paged_win"]
    assert CONFIG["dense_fallback_allowed"] is False
    assert CONFIG["reference"] == "olmo_hybrid_f32"
    # between the chip's two readings (control.py: the program 0.0198-
    # 0.0203, the int4 control 0.512-0.521), with room on both sides
    assert 0.0203 * 3 < CONFIG["reference_tolerance"] < 0.5116 / 3
    assert "control.py" in CONFIG["reference_tolerance_why"]
    ref = ROOT / "servebench/references/olmo_hybrid_f32.py"
    assert ref.read_text() == \
        (ROOT / "butterfly_tpu/models/olmo_hybrid_f32.py").read_text()


def test_the_file_and_a_toy_of_the_family_are_held_to_their_pins():
    from test_servebench_manifest import TOY, held_to_its_pin
    held_to_its_pin(MANIFEST["configs"][8], TOY)
    entry = {"name": "tiny-olmo-hybrid", "reduced": [],
             "source": "tests only",
             "file": str(GDN / "configs" / "tiny-olmo-hybrid.json")}
    manifest = dict(TOY, paths=TOY["paths"] + [str(GDN)])
    held_to_its_pin(entry, manifest)
    with pytest.raises(AssertionError, match="is not the file's"):
        held_to_its_pin(dict(entry, reduced=["num_hidden_layers"]), manifest)


# -- a toy of the family through the harness, from files alone ----------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, `tinyolmo.batch`, made by adding
    files and entries (the toy's configuration; the traffic is the
    tests' own, the reference the benchmark's)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    shutil.copy(GDN / "configs" / "tiny-olmo-hybrid.json",
                root / "servebench" / "configs" / "tiny-olmo-hybrid.json")
    shutil.copy(FILES / "traffic" / "tinyrollout.json",
                root / "servebench" / "traffic" / "tinyrollout.json")
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-olmo-hybrid", "source": "tests only",
                         "file": "servebench/configs/tiny-olmo-hybrid.json",
                         "reduced": [], "why": "a toy for the CPU"})
    m["workloads"].append({"name": "tinyolmo.rollout",
                           "config": "tiny-olmo-hybrid",
                           "traffic": "tinyrollout", "chips": 1,
                           "why": "closed loop on a toy"})
    for e in m["per_layer"]:
        if "olmohybrid7b.batch" in e.get("workloads", ()):
            e["workloads"].append("tinyolmo.rollout")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_a_toy_of_the_family_runs_from_added_files_alone(checkout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"),
               JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, str(checkout / "servebench" / "run.py"),
         "--workload", "tinyolmo.rollout", "--seed", str(2 ** 31 + 56),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = [json.loads(ln) for ln in r.stdout.splitlines()
                 if ln.strip()][-2:]
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    ref = info["refcheck"]
    assert ref["ok"] and ref["max_err"] < 1e-4
    assert ref["reference"] == "olmo_hybrid_f32"
    # the counter reached the line: four slots' decode rows and a chunk's
    # columns a step; the device's metrics did not (a rehearsal prints none)
    got = out["metrics"]
    assert 1 <= got["gdn_rows_per_step"]["value"] <= 4 + 32
    assert "gdn_share" not in got and "gdn_roofline" not in got
    assert "ssm_rows_per_step" not in got
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    blocks = [t for t in ticks if t["ssm_steps"]]
    assert blocks and all(t["ssm_rows"] is not None
                          and t["state_resets"] is not None for t in blocks)
    assert sum(t["state_resets"] for t in ticks if t["state_resets"]) >= 4
    assert all(t["experts_touched"] is None for t in blocks)
    # the server's ready line (its log) says the kind and the layout held
    logs = "".join(p.read_text(errors="replace")
                   for p in (checkout / "chiprun_out").rglob("*.log"))
    assert "Gated DeltaNet" in logs and "heads/g" in logs


def test_a_program_without_the_kind_refuses_the_file_by_name():
    """What the parent of PR 56 does with this cell: the file's "model"
    group names fields its ModelConfig lacks, and
    servebench/launcher.py:model_fields says which before anything is
    built (the launcher exits at once; the chip run is in PERF.md)."""
    import dataclasses
    from unittest import mock

    from butterfly_tpu.core import config as core
    from servebench.launcher import model_fields
    older = dataclasses.make_dataclass("ModelConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(core.ModelConfig)
        if not f.name.startswith(("gdn_", "post_norm", "qk_norm_wide"))])
    with mock.patch.object(core, "ModelConfig", older):
        with pytest.raises(ValueError, match="is no field"):
            model_fields(CONFIG)
    assert model_fields(CONFIG)["gdn_heads"] == 30
