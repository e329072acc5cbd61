"""trinity-ep8.deepthink: the cell's files, the counts of
servebench/window_peaks.py by hand, its three readers on tick records and
a trace written by hand, the file held to its pin, and a toy of the
family through the harness from added files alone (its sliding layers'
rows in rings that wrap)."""
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

from servebench import peaks, window_peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

MANIFEST = load_manifest(ROOT)
NAME = "trinity-ep8.deepthink"
CELL = Cell(MANIFEST, NAME, ROOT)
CONFIG = CELL.config
SWA = "tests/servebench/files_swa"
V5E = "TPU v5 lite"
TEN = ["mistral7b.batch", "mistral7b-bf16-tp4.batch", "smallthinker21b.batch",
       "keye30b.think", "granite4h.rollout", "joyai48b.longthink",
       "xing29b.rollout", "glm5-ep16.think", "olmohybrid7b.batch",
       "jamba2-3b.rollout"]
NEW = ["swa_rows_read_share", "swa_pages_held_share", "swa_attn_roofline"]


# -- the counts, by hand -------------------------------------------------------

@pytest.mark.parametrize("context, slide, full", [
    (100, 100, 100), (4096, 4096, 4096), (6000, 4096, 6000)])
def test_rows_a_step_reads_by_kind(context, slide, full):
    """Six sliding layers read min(context, 4,096) rows a stream, two
    full layers all of them; a row is 2 x 8 x 128 bf16 = 4,096 B."""
    rows = window_peaks.rows_by_kind(CONFIG, [context])
    assert rows == {"slide": 6 * slide, "slide_whole": 6 * context,
                    "full": 2 * full}
    assert peaks.cached_row_bytes(CONFIG) == 4096
    least = window_peaks.paged_least_seconds(CONFIG, V5E, 1, 4, [context])
    by = 4 * (6 * slide + 2 * full) * 4096
    assert least["bytes"] == by and least["bound"] == "memory"
    assert least["least_s"] == pytest.approx(by / 819e9)
    # 48 heads x 128 x 4 operations a row: far under the bf16 peak
    assert least["flops"] == 4 * (6 * slide + 2 * full) * 4 * 48 * 128
    # the part is the whole's: block_roofline counts the same rows
    parts, _ = peaks.step_parts(CONFIG, [context])
    assert parts["rows"] == by / 4


def test_the_cell_s_step_reads_four_gigabytes_of_rows():
    """24 streams at the window's opening, contexts evenly spread over
    2,950 + 0..12,288 (mean 8,100 is the lead-in's; here the steady
    9,100): about nine row-layers in ten of the sliding kind stand at
    the window."""
    contexts = [2950 + 512 * r for r in range(24)]
    rows = window_peaks.rows_by_kind(CONFIG, contexts)
    assert rows["slide"] / rows["slide_whole"] == pytest.approx(0.45, abs=0.02)
    step = (rows["slide"] + rows["full"]) * 4096
    assert 4.0e9 < step < 4.6e9
    past = sum(c >= 4096 for c in contexts) / 24
    assert past > 0.85


def test_pages_held_by_kind_against_one_table():
    assert window_peaks.layers_by_kind(CONFIG) == (6, 2)
    assert window_peaks.ring_pages(CONFIG) == 273
    held = window_peaks.pages_held(CONFIG, [9000] * 24)
    page = 16 * 4096
    assert held["by_kind"] == page * 24 * (6 * 273 + 2 * 563)
    assert held["one_table"] == page * 24 * 8 * 563
    assert held["by_kind"] / held["one_table"] == pytest.approx(0.614, abs=1e-3)
    # a stream under its window: the ring is held whole all the same
    short = window_peaks.pages_held(CONFIG, [100])
    assert short["by_kind"] > short["one_table"]
    # the program's own ring is this one
    from butterfly_tpu.cache.paged import ring_pages
    from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
    from servebench.launcher import model_fields
    sv = CONFIG["serve"]
    assert ring_pages(ModelConfig(**model_fields(CONFIG)), RuntimeConfig(
        max_batch_size=sv["max_batch"], max_seq_len=sv["max_seq"],
        page_size=sv["page_size"],
        decode_steps_per_tick=sv["decode_steps_per_tick"])) == 273


def test_peaks_py_counts_the_file_by_hand_and_what_it_miscounts():
    """The whole step as servebench/peaks.py counts it, and the one
    place it counts too much: `num_experts` 32 (the held) is read as the
    set a token's 4 draws fall in (PERF.md section 7)."""
    h = 3072
    attn = h * 48 * 128 * 2 + h * 8 * 128 * 2          # no gate: 2 % low
    assert peaks.attention_params(CONFIG) == attn
    dense, expert = 3 * h * 12288, 3 * h * 3072
    touched = 32 * (1 - (1 - 4 / 32) ** 24)
    assert touched == pytest.approx(30.7, abs=0.05)
    want = 8 * attn + dense + 7 * (h * 32 + (1 + touched) * expert) \
        + 25024 * h
    assert peaks.streamed_params(CONFIG, 24) == pytest.approx(want)
    # the published odds: 24 rows x 4 draws over 256 touch 80.5 of 256,
    # of which this chip holds an eighth
    truth = 256 * (1 - (1 - 4 / 256) ** 24) / 8
    assert truth == pytest.approx(10.06, abs=0.05)
    over = 7 * (touched - truth) * expert
    assert 4.0e9 < over < 4.2e9        # bytes a step, at one a weight


#: tests/servebench/test_servebench_peaks.py's own five sets of live
#: streams' contexts
CONTEXTS = [[300] * 32, [1164] * 128, [100, 2048, 2049, 4096, 4097, 6000],
            [33 + 211 * i for i in range(32)], []]


@pytest.mark.parametrize("contexts", CONTEXTS, ids=lambda c: f"n{len(c)}")
def test_the_whole_step_of_the_new_file_is_the_sum_of_its_parts(contexts):
    """What test_servebench_peaks.py holds every accepted file to, for
    this file (tests/conftest.py takes that test's five cases of it out:
    its branch for a file with `layer_types` is granite's own, and the
    one for `sliding_window_layout` SmallThinker's 12 of 16): the whole
    is the sum of the parts, weights as `weight_bytes` gives them, every
    one of 8 layers attention, six of them up to the window, no state,
    no index key; four steps are four times one, twice the chips half
    the time; and the paged calls' own count is the rows' part."""
    from servebench import ssm_peaks
    live = len(contexts)
    got = peaks.block_least_seconds(CONFIG, V5E, 1, 1, contexts)
    parts = got["parts"]
    assert set(parts) == {"weights", "rows", "index_keys", "state"}
    assert got["bytes"] == parts["weights"] + parts["rows"] \
        + parts["index_keys"] + parts["state"]
    assert parts["weights"] == peaks.weight_bytes(CONFIG, live)
    assert parts["state"] == parts["index_keys"] == 0.0
    assert not any(ssm_peaks.is_mamba(CONFIG, l) for l in range(8))
    assert parts["rows"] == 4096 * sum(
        2 * c + 6 * min(c, 4096) for c in contexts)
    block = peaks.block_least_seconds(CONFIG, V5E, 2, 4, contexts)
    assert block["bytes"] == 4 * got["bytes"]
    assert block["memory_s"] == pytest.approx(2 * got["memory_s"])
    mine = window_peaks.paged_least_seconds(CONFIG, V5E, 1, 1, contexts)
    assert mine["bytes"] == parts["rows"]


# -- the readers ---------------------------------------------------------------

def tick(seq, read, whole, slide=24 * 273, full=13000, t_wall=100.0):
    return {"seq": seq, "t_wall": t_wall, "swa_rows_read": read,
            "swa_rows_whole": whole, "kv_pages_slide": slide,
            "kv_pages_full": full, "ring_wraps": 0}


def ticks_ctx(ticks):
    return SimpleNamespace(w0=50.0, w1=150.0, wall_minus_mono=0.0,
                           config=CONFIG, info={}, streams=[], ticks=ticks)


def test_rows_read_share_on_tick_records_written_by_hand():
    read = CELL.reader("swa_rows_read_share")
    ctx = ticks_ctx([
        tick(1, 450.0, 1000.0),
        tick(2, 50.0, 1000.0), tick(2, 50.0, 1000.0),     # polled twice
        tick(3, None, None),                  # a tick that drained none
        tick(4, 9e9, 9e9, t_wall=10.0)])      # before the window
    assert read(ctx) == pytest.approx(100 * 500 / 2000)
    assert read(ticks_ctx([tick(1, 800.0, 800.0)])) == 100.0
    # a program without the count (the parent, a cache of one kind)
    assert read(ticks_ctx([{"seq": 1, "t_wall": 100.0}])) is None
    assert read(ticks_ctx([tick(1, None, None)])) is None
    assert read(ticks_ctx([])) is None


def test_pages_held_share_on_tick_records_written_by_hand():
    read = CELL.reader("swa_pages_held_share")
    # 24 rings of 273 in six layers beside 13,000 pages in two, against
    # 13,000 pages in all eight
    one = 100 * (6 * 24 * 273 + 2 * 13000) / (8 * 13000)
    assert read(ticks_ctx([tick(1, 1.0, 2.0)])) == pytest.approx(one)
    assert 60 < one < 65
    two = 100 * (6 * 24 * 273 + 2 * 6500) / (8 * 6500)
    assert read(ticks_ctx([tick(1, 1.0, 2.0), tick(2, 1.0, 2.0, full=6500)])) \
        == pytest.approx((one + two) / 2)
    assert read(ticks_ctx([{"seq": 1, "t_wall": 100.0,
                            "pages_free": 5}])) is None
    assert read(ticks_ctx([tick(1, 1.0, 2.0, full=0)])) is None


def stream(prompt, first, n, end=None):
    times = [first + 0.01 * i for i in range(n)]
    return SimpleNamespace(prompt_len=prompt, times=times, end=end)


def traced_ctx():
    """A capture of 2.0 s: seven runs of the decode block (the first cut
    by the capture's start, the last ending with it) and 0.6 s in the
    paged calls."""
    ops = [["%paged_attention.12 = custom-call", 0.4, 5600],
           ["%paged_attention.13 = custom-call", 0.2, 1400],
           ["%moe_experts.3 = custom-call", 0.7, 4900],
           ["%fusion.77 = bf16[24,3072]", 0.3, 9000]]
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"busy_s": 1.6, "ops": ops, "span0_s": 2.0,
             "module_runs": {"jit_bf_decode_block_win": runs,
                             "jit_flush_paged_window": [[1.9, 0.002]]}}
    from servebench.metrics import live_contexts
    streams = [stream(3000, 0.0, 300), stream(2500, 0.0, 300),
               stream(4000, 5.0, 10)]
    ctx = SimpleNamespace(trace=trace, config=CONFIG, chips=1,
                          device={"kind": V5E}, streams=streams,
                          trace_at=2.95, info={})
    return ctx, live_contexts(streams, 2.95)


def test_attn_roofline_on_a_trace_written_by_hand():
    ctx, contexts = traced_ctx()
    assert len(contexts) == 2
    least = window_peaks.paged_least_seconds(CONFIG, V5E, 1, 4, contexts)
    got = CELL.reader("swa_attn_roofline")(ctx)
    # the calls took 0.6 of the 1.9 s of block runs: that share of a
    # whole block of 0.3 s
    assert got == pytest.approx(100 * least["least_s"] / (0.3 * 0.6 / 1.9))
    assert 0 < got < 100
    assert ctx.info["swa_attn_roofline"]["streams"] == 2
    assert ctx.info["swa_attn_roofline"]["read_s"] == \
        pytest.approx(0.3 * 0.6 / 1.9)
    assert CELL.reader("paged_attn_share")(ctx) == pytest.approx(100 * 0.6 / 1.6)


@pytest.mark.parametrize("metric", NEW)
def test_nothing_to_read_is_none_and_never_raises(metric):
    """What the parent's run of this benchmark gives a metric new here:
    no count in its tick records, no call the pattern tells."""
    read = CELL.reader(metric)
    ctx, _ = traced_ctx()
    base = dict(vars(ctx), w0=50.0, w1=150.0, wall_minus_mono=0.0, ticks=[])
    assert read(SimpleNamespace(**{**base, "trace": {}})) is None
    assert read(SimpleNamespace(**{**base, "trace": None})) is None
    bare = dict(ctx.trace, ops=[o for o in ctx.trace["ops"]
                                if "paged" not in o[0]])
    assert read(SimpleNamespace(**{**base, "trace": bare})) is None
    assert read(SimpleNamespace(**{**base, "trace": bare, "ticks": [
        {"seq": 1, "t_wall": 100.0, "pages_free": 3}]})) is None


# -- the traffic and the entries -----------------------------------------------

def test_deepthink_is_what_the_issue_asked_for_and_fits_the_configuration():
    t = load_traffic(CELL.traffic_path)
    assert (t["kind"], t["clients"], t["rounds"]) == ("closed", 24, 4)
    assert t["prompt"] == {"dist": "loguniform", "lo": 2048, "hi": 4096}
    assert t["output"] == {"dist": "fixed", "value": 12288, "lo": 12288,
                           "hi": 12288}
    assert (t["lead_until_finished"], t["lead_max_s"], t["edge_quiet_ms"],
            t["late_limit_ms"]) == (12, 240, 15, 50)
    sv = CONFIG["serve"]
    plan = make_plan(t, 2 ** 31 + 63, 45.0, CONFIG["vocab_size"], sv["max_seq"])
    assert len(plan.queues) == 24 == sv["max_batch"]
    firsts = sorted(q[0].max_tokens for q in plan.queues)
    assert firsts == [512 * (r + 1) for r in range(24)]
    assert all(r.max_tokens == 12288 for q in plan.queues for r in q[1:])
    longest = max(len(r.tokens) + r.max_tokens for q in plan.queues for r in q)
    assert 16300 < longest <= 4096 + 12288 == sv["max_seq"]
    mean = sum(len(r.tokens) for q in plan.queues for r in q[1:]) / (24 * 4)
    assert 2900 < mean < 3000
    assert all(0 < tok < CONFIG["vocab_size"]
               for q in plan.queues for r in q[:1] for tok in r.tokens)
    # the same work under every seed
    other = make_plan(t, 7, 45.0, CONFIG["vocab_size"], sv["max_seq"])
    assert [[(len(r.tokens), r.max_tokens) for r in q] for q in plan.queues] \
        == [[(len(r.tokens), r.max_tokens) for r in q] for q in other.queues]


def test_the_entries_this_pr_added():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, better, source, layer, moves in (
            ("swa_rows_read_share", "lower", "program_counter",
             "cache manager (cache/)", "tpot_p50_ms"),
            ("swa_pages_held_share", "lower", "program_counter",
             "cache manager (cache/)", "out_tok_s"),
            ("swa_attn_roofline", "higher", "device_trace",
             "kernels (ops/)", "tpot_p50_ms")):
        assert by[name] == {"name": name, "unit": "%", "better": better,
                            "source": source, "layer": layer, "moves": moves,
                            "workloads": [NAME]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-3:] == NEW and names[-4] == "mamba1_roofline"
    # the experts' three counters are an older cell's metrics: the cell
    # joins their lists; mixed_block_ms_p50 reads nothing on the expert
    # call this cell runs (ledger, glm5-ep16.think since PR 61): not there
    for name in ("experts_touched_share", "expert_rows_skew",
                 "experts_local_share"):
        assert by[name]["workloads"][-1] == NAME
    assert by["mixed_block_ms_p50"]["workloads"] == TEN
    for name in ("kv_selected_share", "ssm_rows_per_step", "dsa_share",
                 "latent_attn_share", "sparse_attn_share", "hc_share",
                 "gdn_share", "mamba1_share", "collective_share"):
        assert NAME not in by[name]["workloads"]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells == TEN + [NAME]
    assert [c["name"] for c in MANIFEST["configs"]][-1] == "trinity-large-ep8"
    cfg, cell = MANIFEST["configs"][-1], MANIFEST["workloads"][-1]
    assert cfg["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "layer_types", "sliding_window"]
    assert cfg["source"] == CONFIG["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json"
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("trinity-large-ep8", "deepthink", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    unlisted = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m}
    mine = {m["name"] for m in CELL.per_layer}
    assert unlisted <= mine and "paged_attn_share" in mine
    # block_roofline reads 120 here: servebench/peaks.py counts 30.7
    # touched experts a layer where 11.6 are (PERF.md section 7), and a
    # share over 105 refuses a PR, so the accepted metric was given the
    # list of the ten accepted cells, as the contract lets a new cell do
    assert by["block_roofline"]["workloads"] == TEN
    assert "block_roofline" not in mine
    assert mine - unlisted == {*NEW, "experts_touched_share",
                               "expert_rows_skew", "experts_local_share"}
    assert {m["name"] for m in CELL.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}
    assert MANIFEST["run_seconds"] == 45


def test_the_file_holds_every_published_key_but_the_window_s():
    """Every key of the catalog's copy of the source's config.json under
    its own name and value, but the five cuts (each with its published
    value beside it) and `sliding_window`, which servebench/launcher.py
    refuses at a file's top level: left out, listed, and stated under
    the harness's names."""
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(ln) for ln in catalog.read_text().splitlines()
               if json.loads(ln)["name"] == "Trinity-Large-Preview")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
            assert CONFIG.get(key) != value
        else:
            assert CONFIG[key] == value, key
    assert "sliding_window" not in CONFIG
    assert CONFIG["sliding_window_size"] == 4096 == \
        CONFIG["model"]["sliding_window"] == CONFIG["published"]["sliding_window"]
    # no width is cut, and the cuts are what the deployment says
    for key in CONFIG["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in (
            "hidden_size", "intermediate_size", "moe_intermediate_size")
    assert CONFIG["layer_types"] == CONFIG["published"]["layer_types"][5:13]
    assert CONFIG["sliding_window_layout"] == [
        int(k == "sliding_attention") for k in CONFIG["layer_types"]]
    assert set(CONFIG["assumed"]) >= {"sliding_window", "attn_gate",
                                      "sandwich_norm", "mup_enabled",
                                      "qk_norm", "sliding_rope"}
    assert "TO BE SET" not in json.dumps(CONFIG)


def test_the_file_and_a_toy_of_the_family_are_held_to_their_pins():
    from test_servebench_manifest import TOY, held_to_its_pin
    held_to_its_pin(MANIFEST["configs"][-1], TOY)
    entry = {"name": "tiny-trinity", "reduced": [], "source": "tests only",
             "file": f"{SWA}/configs/tiny-trinity.json"}
    manifest = dict(TOY, paths=TOY["paths"] + [SWA])
    held_to_its_pin(entry, manifest)
    with pytest.raises(AssertionError, match="is not the file's"):
        held_to_its_pin(dict(entry, reduced=["num_hidden_layers"]), manifest)


# -- a toy of the family through the harness, from files alone ----------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with one more cell, `tinytrinity.deepthink`, made by
    adding files and entries (the toy's configuration and its traffic;
    the reference is the benchmark's)."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "servebench", root / "servebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "butterfly_tpu", root / "butterfly_tpu")
    shutil.copy(ROOT / SWA / "configs" / "tiny-trinity.json",
                root / "servebench" / "configs" / "tiny-trinity.json")
    shutil.copy(ROOT / SWA / "traffic" / "tinydeepthink.json",
                root / "servebench" / "traffic" / "tinydeepthink.json")
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-trinity", "source": "tests only",
                         "file": "servebench/configs/tiny-trinity.json",
                         "reduced": [], "why": "a toy for the CPU"})
    m["workloads"].append({"name": "tinytrinity.deepthink",
                           "config": "tiny-trinity",
                           "traffic": "tinydeepthink", "chips": 1,
                           "why": "closed loop on a toy"})
    for e in m["per_layer"]:
        if NAME in e.get("workloads", ()):
            e["workloads"].append("tinytrinity.deepthink")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def test_a_toy_of_the_family_runs_from_added_files_alone(checkout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"),
               JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, str(checkout / "servebench" / "run.py"),
         "--workload", "tinytrinity.deepthink", "--seed", str(2 ** 31 + 63),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = [json.loads(ln) for ln in r.stdout.splitlines()
                 if ln.strip()][-2:]
    assert out["correct"] is True and out["failed"] == 0, r.stderr[-3000:]
    ref = info["refcheck"]
    assert ref["ok"] and ref["max_err"] < 1e-4
    assert ref["reference"] == "trinity_f32"
    got = out["metrics"]
    # contexts of 40-260 under a window of 8: a few rows in a hundred
    assert 0 < got["swa_rows_read_share"]["value"] < 40
    # four rings of 10 pages in four layers beside the one full layer's
    # pages: under what one table would hold once streams are long
    assert 0 < got["swa_pages_held_share"]["value"]
    assert "swa_attn_roofline" not in got     # a rehearsal prints no device metric
    assert 0 < got["experts_touched_share"]["value"] <= 100
    assert got["preemptions"]["value"] == 0
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    blocks = [t for t in ticks if t["swa_rows_whole"]]
    assert blocks and all(t["kv_pages_slide"] % 10 == 0 for t in ticks)
    # streams of 220-260 pass a ring of 160 rows
    assert sum(t["ring_wraps"] for t in ticks) >= 1
    logs = "".join(p.read_text(errors="replace")
                   for p in (checkout / "chiprun_out").rglob("*.log"))
    assert '"ring_pages": 10' in logs


def test_a_program_without_the_fields_refuses_the_file_by_name():
    """What the parent of PR 63 does with this cell: the file's "model"
    group names fields its ModelConfig lacks, and
    servebench/launcher.py:model_fields says which before anything is
    built (the launcher exits at once; the chip run is in PERF.md)."""
    import dataclasses
    from unittest import mock

    from butterfly_tpu.core import config as core
    from servebench.launcher import model_fields
    older = dataclasses.make_dataclass("ModelConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(core.ModelConfig)
        if f.name not in ("attn_gate", "sandwich_norm", "mup_embed")])
    with mock.patch.object(core, "ModelConfig", older):
        with pytest.raises(ValueError, match="'sandwich_norm' is no field"):
            model_fields(CONFIG)
    assert model_fields(CONFIG)["attn_gate"] is True
