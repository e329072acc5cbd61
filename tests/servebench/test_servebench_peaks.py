"""What PR 43 makes `servebench/peaks.py` count: each layer by its kind
(attention, latent attention, a Mamba-2 mixer), what a cached row holds,
and the rows a step reads of each live stream (all, a window's, a
selection's), so that the whole step's bytes are the sum of the parts the
per-kernel modules give, for every accepted file at any contexts."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench import peaks, sparse_peaks, ssm_peaks  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402

MANIFEST = load_manifest(ROOT)
V5E = "TPU v5 lite"
#: live streams' contexts: one short, a cell's own mean, past a window of
#: 4,096 and a selection of 2,048, every one different, and an idle step
CONTEXTS = [[300] * 32, [1164] * 128, [100, 2048, 2049, 4096, 4097, 6000],
            [33 + 211 * i for i in range(32)], []]


def file_of(name):
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == name)
    return json.loads((ROOT / cfg["file"]).read_text())


# -- every accepted file: the whole is the sum of the parts -------------------

@pytest.mark.parametrize("contexts", CONTEXTS, ids=lambda c: f"n{len(c)}")
@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_the_whole_step_is_the_sum_of_the_parts(cfg, contexts):
    """Weights streamed, cached rows read under each layer's rule, index
    keys, recurrent state read and written: `bytes` is their sum, and the
    parts a kernel of its own serves are what `ssm_least_seconds` and
    `sparse_least_seconds` give for the same streams."""
    config = json.loads((ROOT / cfg["file"]).read_text())
    L, live = config["num_hidden_layers"], len(contexts)
    got = peaks.block_least_seconds(config, V5E, 1, 1, contexts)
    parts = got["parts"]
    assert set(parts) == {"weights", "rows", "index_keys", "state"}
    assert got["bytes"] == parts["weights"] + parts["rows"] \
        + parts["index_keys"] + parts["state"]
    assert parts["weights"] == peaks.weight_bytes(config, live)
    per = 1.0 if config["serve"]["quant"] == "int8" else 2.0
    row = peaks.cached_row_bytes(config)
    attention = [l for l in range(L) if not ssm_peaks.is_mamba(config, l)]
    if "layer_types" in config:          # granite: nine mixers of ten layers
        mixers = peaks.ssm_least_seconds(config, V5E, 1, 1, live)
        assert len(attention) == 1
        assert mixers["bytes"] == 9 * ssm_peaks.proj_params(config) * per \
            + parts["state"]
        assert parts["state"] == 9 * live * ssm_peaks.state_bytes(config)
        assert parts["rows"] == sum(contexts) * row
    else:
        assert parts["state"] == 0.0 and len(attention) == L
    if "sa_config" in config:            # Keye: every layer selects
        path = peaks.sparse_least_seconds(config, V5E, 1, 1, contexts)
        assert path["bytes"] == parts["rows"] + parts["index_keys"] \
            + L * sparse_peaks.indexer_params(config) * 2
        assert parts["rows"] == L * sum(min(c, 2048) for c in contexts) * row
        assert parts["index_keys"] == L * sum(contexts) * 128
    else:
        assert parts["index_keys"] == 0.0
    if "sliding_window_layout" in config:   # SmallThinker: 12 of 16 slide
        assert parts["rows"] == row * sum(
            4 * c + 12 * min(c, 4096) for c in contexts)
    if config["name"].startswith("mistral"):
        assert parts["rows"] == L * sum(contexts) * row
    # four steps are four times one; twice the chips, half the time
    block = peaks.block_least_seconds(config, V5E, 2, 4, contexts)
    assert block["bytes"] == 4 * got["bytes"]
    assert block["memory_s"] == pytest.approx(2 * got["memory_s"])


def test_a_file_is_counted_over_the_layers_it_runs():
    """Granite's `layer_types` is the source's list of 40; ten run. All
    40: four attention layers hold rows, 36 mixers hold state."""
    config = file_of("granite-4.0-h-small")
    ten, _ = peaks.step_parts(config, [1000] * 4)
    forty, _ = peaks.step_parts(dict(config, num_hidden_layers=40),
                                  [1000] * 4)
    assert forty["rows"] == 4 * ten["rows"] == 4 * 4000 * 4096
    assert forty["state"] == 4 * ten["state"]
    assert [peaks.rows_read(config, l, 1000) for l in range(10)] == \
        [0] * 5 + [1000] + [0] * 4
    assert peaks.mixer_params(config, 5) == 41_943_040
    assert peaks.mixer_params(config, 4) == 102_236_160


# -- a sliding file ------------------------------------------------------------

@pytest.mark.parametrize("context, slides", [(100, 100), (4096, 4096),
                                             (6000, 4096)])
def test_a_sliding_layer_reads_its_window_and_a_full_layer_the_context(
        context, slides):
    """smallthinker-21b-a3b: of every four layers the first is full, three
    slide over 4,096; a row is 2 x 4 heads x 128 in bf16."""
    config = file_of("smallthinker-21b-a3b")
    assert config["sliding_window_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert peaks.cached_row_bytes(config) == 2048
    assert [peaks.rows_read(config, l, context) for l in range(4)] == \
        [context, slides, slides, slides]
    got, _ = peaks.step_parts(config, [context] * 32)
    assert got["rows"] == 32 * (4 * context + 12 * slides) * 2048
    # what the count was until PR 43: sixteen layers at the whole context
    parents = 32 * 16 * context * 2048
    assert (got["rows"] == parents) == (context <= 4096)
    assert got["index_keys"] == got["state"] == 0.0


def test_smallthinker_under_its_window_is_the_parents_count_to_the_bit():
    """The cell's contexts stay under 384: every field that binds equal
    (==) to the parent's formula (PR 34) written out by hand."""
    config = file_of("smallthinker-21b-a3b")
    contexts = [40 + 10 * i for i in range(31)]
    attn = 2560 * 28 * 128 * 2 + 2560 * 4 * 128 * 2
    expert, head = 3 * 2560 * 768, 151936 * 2560
    read = 0 + 64 * (1.0 - (1.0 - 6 / 64) ** 31.0)
    params = 16 * attn + 0 * (3 * 2560 * 768) \
        + (16 - 0) * (2560 * 64 + read * expert) + head
    by = 4 * (params * 1.0 + sum(contexts) * (16 * 2 * 4 * (2.0 * 128)))
    got = peaks.block_least_seconds(config, V5E, 1, 4, contexts)
    assert got["bytes"] == by
    assert got["memory_s"] == got["least_s"] == by / (1 * 819e9)
    assert got["bound"] == "memory"


# -- a latent file -------------------------------------------------------------

#: the published keys of the kind (config.json of
#: jdopensource/JoyAI-LLM-Flash), at one dense and five sparse layers in
#: int8: `head_dim` and `num_key_value_heads` are there and say nothing
#: about what is cached
LATENT = dict(hidden_size=2048, num_hidden_layers=6, num_attention_heads=32,
              num_key_value_heads=32, head_dim=64, kv_lora_rank=512,
              q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
              qk_head_dim=192, v_head_dim=128, intermediate_size=7168,
              moe_intermediate_size=768, n_routed_experts=256,
              n_shared_experts=1, num_experts_per_tok=8,
              first_k_dense_replace=1, vocab_size=129280,
              serve={"quant": "int8", "kv_quant": "none"})


def test_a_latent_row_is_one_latent_a_token_and_layer():
    assert peaks.cached_row_bytes(LATENT) == (512 + 64) * 2 == 1152
    # whatever the two keys a grouped-query file is counted by say
    other = dict(LATENT, head_dim=128, num_key_value_heads=8)
    assert peaks.cached_row_bytes(other) == 1152
    assert peaks.attention_params(other) == peaks.attention_params(LATENT)
    # the same keys without the latent: 2 x 32 heads x 64 x 2 B
    plain = {k: v for k, v in LATENT.items() if k != "kv_lora_rank"}
    assert peaks.cached_row_bytes(plain) == 8192
    assert peaks.attention_params(plain) == 2048 * 64 * (32 + 32) * 2 \
        == 16_777_216


def test_latent_attention_s_five_projections():
    q = 2048 * 1536 + 1536 * 32 * (128 + 64)
    kv = 2048 * (512 + 64) + 512 * 32 * (128 + 128)
    out = 32 * 128 * 2048
    assert (q, kv, out) == (12_582_912, 5_373_952, 8_388_608)
    assert peaks.attention_params(LATENT) == q + kv + out == 26_345_472
    # a null `q_lora_rank`: the query projected directly
    direct = dict(LATENT, q_lora_rank=None)
    assert peaks.attention_params(direct) == \
        2048 * 32 * 192 + kv + out == 26_345_472
    # absorbed, a row costs scores over 576 and a sum over 512, 32 heads:
    # 60 operations a byte against the chip's 240
    assert peaks.row_flops(LATENT) == 2 * 32 * (2 * 512 + 64) == 69_632
    assert peaks.row_flops(LATENT) / 1152 == pytest.approx(60.4, abs=0.1)
    assert 197e12 / 819e9 == pytest.approx(240.5, abs=0.1)


def test_a_latent_file_s_experts_are_found_and_its_step_is_7_4_gb():
    """ISSUE 43's cell: 64 streams of 3,750. Under the parent's count the
    cache alone was 240,000 x 6 x 8,192 = 11.80 GB and no expert was
    found under `n_routed_experts`."""
    assert peaks.num_experts(LATENT) == 256
    touched = 256 * (1 - (1 - 8 / 256) ** 64)
    assert touched == pytest.approx(222.4, abs=0.05)
    expert = 3 * 2048 * 768
    weights = 6 * 26_345_472 + 3 * 2048 * 7168 \
        + 5 * (2048 * 256 + (1 + touched) * expert) + 129280 * 2048
    assert weights == pytest.approx(5.74e9, rel=2e-3)
    got = peaks.block_least_seconds(LATENT, V5E, 1, 1, [3750] * 64)
    assert got["parts"] == {"weights": pytest.approx(weights, rel=1e-12),
                            "rows": 240_000 * 6 * 1152, "index_keys": 0.0,
                            "state": 0.0}
    assert got["parts"]["rows"] == 1_658_880_000
    assert got["bytes"] == pytest.approx(7.40e9, rel=2e-3)
    assert 240_000 * 6 * 8192 == 11_796_480_000
    assert got["memory_s"] == pytest.approx(9.0e-3, rel=0.01)
    # the compute bound is stated, not assumed: a position meets 9 experts
    meets = 6 * 26_345_472 + 3 * 2048 * 7168 \
        + 5 * (2048 * 256 + 9 * expert) + 129280 * 2048
    assert peaks.matmul_params(LATENT) == meets
    assert got["flops"] == 2.0 * meets * 64 + 240_000 * 6 * 69_632
    assert got["bound"] == "memory" and got["compute_s"] < 1e-3
    # mistral's keys count as they did: no latent, no experts
    assert peaks.num_experts(file_of("mistral-7b-v0.3")) == 0


# -- the reader ----------------------------------------------------------------

def stream(prompt, first, n, end=None):
    return SimpleNamespace(prompt_len=prompt, end=end,
                           times=[first + 0.1 * i for i in range(n)])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_block_roofline_on_a_trace_written_by_hand(cell):
    """Three streams generate at the trace's middle (contexts 130, 230 and
    94); five whole runs of 0.3 s. The reader leaves what it counted in
    the info line."""
    c = Cell(MANIFEST, cell, ROOT)
    runs = [[0.0, 0.1], [0.1, 0.3], [0.4, 0.3], [0.7, 0.3], [1.0, 0.3],
            [1.3, 0.3], [1.6, 0.3]]
    trace = {"span0_s": 2.0, "module_runs": {"jit_bf_mixed_block_win": runs}}
    streams = [stream(100, 0.0, 30), stream(200, 0.0, 30),
               stream(64, 0.0, 300), stream(250, 5.0, 10),
               stream(90, 0.0, 5, end=0.6)]
    ctx = SimpleNamespace(trace=trace, config=c.config, chips=c.chips,
                          device={"kind": V5E}, streams=streams,
                          trace_at=2.95, info={})
    least = peaks.block_least_seconds(c.config, V5E, c.chips, 4,
                                      [130, 230, 94])
    assert c.reader("block_roofline")(ctx) == \
        pytest.approx(100 * least["least_s"] / 0.3)
    assert ctx.info["block_roofline"] == dict(
        least, contexts=[130, 230, 94], block_s=pytest.approx(0.3))
    json.dumps(ctx.info)            # the info line is printed as JSON
    ctx.trace = {}
    assert c.reader("block_roofline")(ctx) is None
