"""Trinity (arcee-ai/Trinity-Large-Preview, `afmoe`) on every path the
server runs, against its plain reference
(butterfly_tpu/models/trinity_f32.py; the benchmark's copy is
servebench/references/trinity_f32.py): a toy of the model's shape (six
queries a KV head, norms on heads, the output gate, a norm on both sides
of a sublayer, the embedding scaled, layers S S S F S with a window of 8,
a leading dense layer before 8 sigmoid-routed experts top 2 with a bias,
a scale and a shared expert), seeded random weights, float32, sequences
of 40 so that a context passes three windows. Logits, not tokens."""
import filecmp
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, tiny, trinity_large,
    trinity_large_ep8)
from butterfly_tpu.models import trinity_f32 as ref
from butterfly_tpu.models.common import (
    Model, forward, gate_unsupported, init_params)
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)
from packed_driver import err, leaf_of, scripted_run

ROOT = Path(__file__).resolve().parent.parent
CFG = tiny("trinity", dtype="float32", param_dtype="float32")
T = 40
#: float32 on both sides on the CPU (the reference's products at
#: "highest", the program's at the CPU's float32): readings of 4e-7 to
#: 2e-6 of a row's spread; bfloat16 in place of float32 reads 1e-2
TOL = 2e-5

fwd = jax.jit(forward, static_argnums=(1, 5))


def file_config(cfg: ModelConfig, **over) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    kinds = ["sliding_attention" if s else "full_attention"
             for s in cfg.sliding_window_layout[:cfg.num_layers]]
    model = {"experts_held": cfg.experts_held,
             "experts_first": cfg.experts_first} if cfg.experts_held else {}
    return dict(
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        num_dense_layers=cfg.first_k_dense, layer_types=kinds,
        sliding_window_size=cfg.sliding_window,
        num_experts_per_tok=cfg.num_experts_per_tok,
        route_scale=cfg.routed_scaling_factor, model=model, **over)


def seeded(cfg, key=0):
    """Weights with norms that are not constants, so that a norm put in
    the wrong place, or left out, shows."""
    p = init_params(cfg, jax.random.PRNGKey(key))
    ks = iter(jax.random.split(jax.random.PRNGKey(key + 1), 8))
    for name in ("ln1", "ln2", "ln1_post", "ln2_post"):
        if name in p["layers"]:
            s = p["layers"][name]["scale"]
            p["layers"][name]["scale"] = s * (1 + 0.3 * jax.random.normal(
                next(ks), s.shape))
    for name in ("q_norm", "k_norm"):
        s = p["layers"]["attn"][name]["scale"]
        p["layers"]["attn"][name]["scale"] = 1 + 0.3 * jax.random.normal(
            next(ks), s.shape)
    return p


@pytest.fixture(scope="module")
def params():
    return seeded(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (3, T))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of the sequences: [3, T, V]."""
    return np.stack([np.asarray(ref.logits(t, leaf_of(params),
                                           file_config(CFG)))
                     for t in tokens])


def served(cfg, params, toks, prefill=28):
    """[T, V]: a prefill of `prefill` tokens through the contiguous
    cache, then decode calls of one token through it."""
    cache = Model(cfg).init_cache(1, 64)
    got, cache = fwd(params, cfg, jnp.asarray([toks[:prefill]]), cache)
    rows = [np.asarray(got[0])]
    for j in range(prefill, len(toks)):
        g, cache = fwd(params, cfg, jnp.asarray([toks[j:j + 1]]), cache)
        rows.append(np.asarray(g[0]))
    return np.concatenate(rows)


def worst(got, want):
    return max(err(g, w) for g, w in zip(got, want))


# -- (a) the contiguous cache: prefill, then decode -------------------------

def test_prefill_then_decode_through_the_contiguous_cache(params, tokens,
                                                          want):
    """Positions to 39 under a window of 8: the decode rows stand past
    four windows, in layers that slide and rotate beside one that does
    neither."""
    assert worst(served(CFG, params, tokens[0]), want[0]) < TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance(params, tokens,
                                                         want):
    low = CFG.replace(dtype="bfloat16")
    assert worst(served(low, params, tokens[0]), want[0]) > 50 * TOL


@pytest.mark.parametrize("field", ["attn_gate", "sandwich_norm", "mup_embed"])
def test_each_new_field_is_held_by_the_reference(params, tokens, want, field):
    """The same weights under a ModelConfig with ONE of the three fields
    off (the leaves it would read are then not read): far outside the
    tolerance the sound program keeps. On: the test above."""
    off = CFG.replace(**{field: False})
    assert worst(served(off, params, tokens[0]), want[0]) > 1000 * TOL


@pytest.mark.parametrize("layout, what", [
    ("rope_layout", "the full layer rotates too"),
    ("sliding_window_layout", "the full layer slides too")])
def test_a_layer_of_the_wrong_kind_is_seen(params, tokens, want, layout,
                                           what):
    wrong = CFG.replace(**{layout: (1,) * CFG.num_layers})
    assert worst(served(wrong, params, tokens[0]), want[0]) > 1000 * TOL, what


def test_the_new_fields_default_off_and_move_no_other_model():
    plain = ModelConfig()
    assert not (plain.attn_gate or plain.sandwich_norm or plain.mup_embed)
    for name, make in PRESETS.items():
        on = name.startswith("trinity")
        cfg = make()
        assert (cfg.attn_gate, cfg.sandwich_norm, cfg.mup_embed) == (on,) * 3
    leaves = init_params(tiny("llama"), jax.random.PRNGKey(0))["layers"]
    assert "wg" not in leaves["attn"] and "ln1_post" not in leaves


@pytest.mark.parametrize("bad, match", [
    (dict(sandwich_norm=True, post_norm=True), "sandwich_norm beside post_norm"),
    (dict(sandwich_norm=True, hc_mult=2), "sandwich_norm beside hc_mult"),
    (dict(attn_gate=True, use_bias=True), "attn_gate beside"),
])
def test_what_the_new_fields_do_not_stand_beside_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny("llama", **bad)


def test_a_path_with_a_layer_body_of_its_own_refuses_the_family():
    with pytest.raises(NotImplementedError, match="attn_gate"):
        gate_unsupported(CFG, "the sequence-parallel prefill lane")
    gate_unsupported(tiny("llama"), "the sequence-parallel prefill lane")


# -- (b) the packed step over the cache by kind ------------------------------

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("ring", [0, 8], ids=["one_table", "rings"])
def test_the_packed_step_is_the_reference(params, tokens, want, ring,
                                          use_kernel):
    """Chunks of 6 and decode rows to position 30 through the
    write-combined window and its flush, the sliding layers' rows in
    rings of 8 pages of 4 (or under the one table), kernels on
    (interpreted) and off: every row the head read, against the
    reference's. A leading dense layer and four layers of experts run as
    runs, cut again where sliding layers meet the full one."""
    out, drv, _ = scripted_run(params, tokens, CFG, use_kernel=use_kernel,
                               ring=ring)
    assert drv.cache.by_kind == bool(ring)
    assert max(p for _, p, _ in out) >= 3 * CFG.sliding_window
    assert max(err(row, want[s, p]) for s, p, row in out) < TOL
    load = np.stack(drv.loads)
    # experts touched, fullest, mean; by kind the sliding layers' rows
    # read and what they would have read with no window
    assert load.shape[1] == (5 if ring else 3)
    assert (load[:, 0] <= CFG.num_experts).all()


# -- (c) one chip's share of the experts --------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(params, tokens):
    """Eight chips' routed parts, each of ONE expert of the toy's eight
    (experts_held 1 from experts_first r), plus the shared expert once,
    are the uncut layer's feed-forward."""
    leaf = leaf_of(params)
    h = jax.random.normal(jax.random.PRNGKey(9), (12, CFG.hidden_size))
    whole = ref.feed_forward(h, leaf, "sparse/", 1, file_config(CFG))
    shared = ref.feed_forward(
        h, leaf, "sparse/", 1,
        file_config(CFG.replace(experts_held=1, experts_first=0)),
        shared=True)
    parts = shared
    for r in range(1, CFG.num_experts):
        def cut(path, layer=None, r=r):
            # rank r's leaves hold its own expert at index 0
            if path.startswith("sparse/moe/w_") and isinstance(layer, tuple):
                return leaf(path, (layer[0], layer[1] + r))
            return leaf(path, layer)
        parts = parts + ref.feed_forward(
            h, cut, "sparse/", 1,
            file_config(CFG.replace(experts_held=1, experts_first=r)),
            shared=False)
    assert float(jnp.max(jnp.abs(parts - whole))) < 1e-5


def test_a_share_through_the_program_is_the_reference_s(params, tokens):
    """The program holding experts 2-4 of 8 (the leaves cut to them)
    against the reference told the same share."""
    cfg = CFG.replace(experts_held=3, experts_first=2)
    cut = jax.tree.map(lambda a: a, params)
    cut["sparse"] = {**params["sparse"], "moe": {
        k: (v[:, 2:5] if k.startswith("w_") else v)
        for k, v in params["sparse"]["moe"].items()}}
    want = np.asarray(ref.logits(tokens[1], leaf_of(cut), file_config(cfg)))
    assert worst(served(cfg, cut, tokens[1]), want) < TOL


# -- (d) int8 codes, by leaf ---------------------------------------------------

def test_the_gate_is_coded_as_the_other_four_and_the_small_leaves_stay():
    q = quantize_int8(init_params(CFG, jax.random.PRNGKey(0)), CFG)
    attn = q["layers"]["attn"]
    for name in ("wq", "wk", "wv", "wg", "wo"):
        assert is_quantized_leaf(attn[name]), name
    # one scale an output channel: the gate's as the query's
    assert attn["wg"]["s"].shape == attn["wq"]["s"].shape == (5, 1, 12, 16)
    for leaf in (q["sparse"]["moe"]["router"], q["sparse"]["moe"]["router_bias"],
                 q["embed"]["tok"], q["layers"]["ln1_post"]["scale"],
                 attn["q_norm"]["scale"]):
        assert not is_quantized_leaf(leaf)
    by_leaf = init_params_by_leaf(CFG, jax.random.PRNGKey(0), quant="int8")
    assert jax.tree.structure(by_leaf) == jax.tree.structure(q)
    # the norms behind the sublayers are seeded at (2 L)^-1/2 both ways
    for tree in (q, by_leaf):
        post = np.asarray(tree["layers"]["ln2_post"]["scale"], np.float32)
        assert np.allclose(post, 10 ** -0.5, rtol=1e-2)
        assert np.allclose(np.asarray(tree["layers"]["ln1"]["scale"],
                                      np.float32), 1.0)


def test_int8_codes_stay_near_the_float_model(params, tokens, want):
    q = quantize_int8(params, CFG)
    got = served(CFG, q, tokens[0])
    assert TOL < worst(got, want[0]) < 0.2


# -- (e) the presets, the files, the loader ------------------------------------

def test_the_preset_counts_the_published_parameters():
    """398.6 G with the gate, 13.37 G a token: the published 400B-A13B."""
    cfg = trinity_large()
    D, Nq, Kv, H = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = 3 * D * Nq * H + 2 * D * Kv * H
    dense = attn + 3 * D * cfg.intermediate_size
    expert = 3 * D * cfg.moe_intermediate_size
    sparse = attn + D * cfg.num_experts + expert
    total = 6 * dense + 54 * (sparse + 256 * expert) + 2 * cfg.vocab_size * D
    active = 6 * dense + 54 * (sparse + 4 * expert) + 2 * cfg.vocab_size * D
    assert round(total / 1e9, 1) == 398.6 and 13.3 < active / 1e9 < 13.4
    assert cfg.slides == (1, 1, 1, 0) * 15
    cut = trinity_large_ep8()
    assert (cut.num_layers, cut.first_k_dense, cut.experts_held,
            cut.vocab_size) == (8, 1, 32, 25024)
    assert cut.slides == (1, 1, 0, 1, 1, 1, 0, 1) == cut.rope_layout
    # published layers 5-12: layer i is full where (i + 1) % 4 == 0
    assert [int((i + 1) % 4 != 0) for i in range(5, 13)] == list(cut.slides)


def test_the_benchmark_s_file_builds_the_preset_and_its_reference_is_this_one():
    import json
    from servebench.launcher import model_fields
    config = json.loads((ROOT / "servebench/configs/trinity-large-ep8.json")
                        .read_text())
    built = ModelConfig(**model_fields(config))
    assert built == trinity_large_ep8().replace(dtype="bfloat16")
    assert filecmp.cmp(ROOT / "butterfly_tpu/models/trinity_f32.py",
                       ROOT / "servebench/references/trinity_f32.py",
                       shallow=False)
    # at the cell's runtime the sliding layers' rows lie in rings
    from butterfly_tpu.cache.paged import ring_pages
    sv = config["serve"]
    rt = RuntimeConfig(max_batch_size=sv["max_batch"],
                       max_seq_len=sv["max_seq"], page_size=sv["page_size"],
                       decode_steps_per_tick=sv["decode_steps_per_tick"])
    assert ring_pages(built, rt) == 273


def test_no_checkpoint_converter_refuses_the_family_by_name(tmp_path):
    from butterfly_tpu.ckpt.load import load_checkpoint
    with pytest.raises(ValueError, match="no checkpoint converter for arch "
                                         "'trinity'"):
        load_checkpoint(str(tmp_path), CFG)


def test_the_cli_knows_the_family_s_toy_and_its_presets():
    from types import SimpleNamespace
    from butterfly_tpu.serve.cli import build_parser, resolve_model
    m = resolve_model(SimpleNamespace(model="tiny-trinity", dtype=None))
    assert m.cfg == CFG
    assert {"trinity-large", "trinity-large-ep8"} <= set(PRESETS)
    args = build_parser().parse_args(
        ["serve", "--model", "trinity-large-ep8", "--num-pages", "19800"])
    assert args.num_pages == 19800
