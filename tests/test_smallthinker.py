"""SmallThinker on every path the server runs, against its plain
reference (butterfly_tpu/models/smallthinker_f32.py): a toy of the
model's shape (7 queries a KV head, 8 ReGLU experts top 3 routed on the
attention's normed input, the pattern [0, 1, 1, 1], a sliding window of
8), seeded random weights, float32, sequences of 40 so that the window
binds. Logits, not tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache.paged import (
    flush_paged_window, init_kv_window, init_paged_cache, paged_forward,
    paged_forward_packed, paged_forward_window)
from butterfly_tpu.core.config import (
    ModelConfig, RuntimeConfig, smallthinker_21b_a3b, tiny)
from butterfly_tpu.models import smallthinker_f32 as ref
from butterfly_tpu.models.common import (
    Model, expert_load, forward, layer_stack, make_mask, route_tokens)
from butterfly_tpu.ops.paged_attention import paged_attention

CFG = tiny("smallthinker", dtype="float32", param_dtype="float32")
T = 40
#: float32 on both sides on the CPU
TOL = 2e-5


def file_config(cfg: ModelConfig, **over) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        num_hidden_layers=cfg.num_layers,
        moe_num_active_primary_experts=cfg.num_experts_per_tok,
        sliding_window_size=cfg.sliding_window,
        sliding_window_layout=list(cfg.sliding_window_layout),
        rope_layout=list(cfg.rope_layout), **over)


def leaf_of(params):
    def leaf(path, layer=None):
        node = params
        for key in path.split("/"):
            node = node[key]
        return (node if layer is None else node[layer]).astype(jnp.float32)
    return leaf


@pytest.fixture(scope="module")
def params():
    p = Model(CFG).init(jax.random.PRNGKey(0))
    # norms that are not all ones, so that a norm put in the wrong place shows
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    p["layers"]["ln1"]["scale"] = 1 + 0.3 * jax.random.normal(
        k1, p["layers"]["ln1"]["scale"].shape)
    p["layers"]["ln2"]["scale"] = 1 + 0.3 * jax.random.normal(
        k2, p["layers"]["ln2"]["scale"].shape)
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (2, T))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of both sequences: [2, T, V]."""
    return np.stack([np.asarray(ref.logits(t, leaf_of(params),
                                           file_config(CFG)))
                     for t in tokens])


def worst(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)))


# -- (a) the contiguous cache: prefill, then decode -------------------------

def test_prefill_then_decode_through_the_contiguous_cache(params, tokens, want):
    model = Model(CFG)
    cache = model.init_cache(2, 64)
    got, cache = model(params, jnp.asarray(tokens[:, :24]), cache)
    rows = [got]
    for t in range(24, T):
        got, cache = model(params, jnp.asarray(tokens[:, t:t + 1]), cache)
        rows.append(got)
    assert worst(jnp.concatenate(rows, axis=1), want) < TOL


# -- (d) the flash kernels: a prompt, and a warm chunk behind it ------------

def test_flash_prefill_and_a_warm_chunk(params, tokens, want):
    flash = CFG.replace(attn_impl="flash")
    cache = Model(CFG).init_cache(2, 64)
    a, cache = forward(params, flash, jnp.asarray(tokens[:, :24]), cache,
                       fresh=True)
    b, cache = forward(params, flash, jnp.asarray(tokens[:, 24:]), cache)
    assert worst(jnp.concatenate([a, b], axis=1), want) < TOL


# -- (b) the paged path -----------------------------------------------------

RT = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=4)


def paged_cache(rt=RT):
    cache = init_paged_cache(CFG, rt)
    per = rt.max_seq_len // rt.page_size
    table = np.full(np.asarray(cache.page_table).shape, cache.null_page,
                    np.int32)
    for b in range(2):
        table[b, :per] = np.arange(b * per, (b + 1) * per)
    return cache._replace(page_table=jnp.asarray(table))


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_paged_prefill_chunks_then_decode(params, tokens, want, kernels):
    """A fresh chunk, a warm chunk, then decode steps one token at a
    time; with kernels the flash pair and the Mosaic paged kernel
    (interpreted), with the window of layers 1-3 skipping pages."""
    cfg = CFG.replace(attn_impl="flash") if kernels else CFG
    cache = paged_cache()
    a, cache = paged_forward(params, cfg, jnp.asarray(tokens[:, :16]), cache,
                             fresh=True)
    b, cache = paged_forward(params, cfg, jnp.asarray(tokens[:, 16:28]),
                             cache)
    rows = [a, b]
    for t in range(28, T):
        got, cache = paged_forward(params, cfg,
                                   jnp.asarray(tokens[:, t:t + 1]), cache,
                                   use_kernel=kernels)
        rows.append(got)
    assert worst(jnp.concatenate(rows, axis=1), want) < TOL


# -- (b, e) the write-combined window, (c) the packed mixed step ------------

@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_windowed_decode_through_the_paged_kernel(params, tokens, want,
                                                  kernels):
    """Decode through paged_forward_window: the staged entries beside
    the pool, a flush every 5 steps, so that the sliding layers' lower
    bound falls in the pool, on a page edge and inside the staged run."""
    cache = paged_cache()
    _, cache = paged_forward(params, CFG, jnp.asarray(tokens[:, :20]), cache,
                             fresh=True)
    window = init_kv_window(cache, 8)
    wlen = jnp.zeros((2,), jnp.int32)
    rows = []
    for t in range(20, T):
        got, window = paged_forward_window(
            params, CFG, jnp.asarray(tokens[:, t:t + 1]), cache, window,
            wlen, use_kernel=kernels)
        wlen = wlen + 1
        rows.append(got)
        if (t - 19) % 5 == 0:
            cache, wlen, _ = flush_paged_window(cache, window, wlen)
    assert worst(jnp.concatenate(rows, axis=1), want[:, 20:]) < TOL


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_step_with_a_chunk_beside_decode_rows(params, tokens, want,
                                                     kernels, windowed):
    """Slot 0 decodes from position 30 while slot 1's prompt enters in
    chunks of 4 from position 12: every packed step computes one decode
    row and one chunk, both read by the head."""
    C = 4
    cache = paged_cache()
    lens = np.array([30, 12])
    pre = np.zeros((2, 30), np.int64)
    pre[0], pre[1, :12] = tokens[0, :30], tokens[1, :12]
    # both prompts' beginnings through the plain paged path, each to its length
    _, cache = paged_forward(params, CFG, jnp.asarray(pre), cache, fresh=True)
    cache = cache._replace(lengths=jnp.asarray(lens, jnp.int32))
    window = wlen = None
    if windowed:
        window, wlen = init_kv_window(cache, 8), jnp.zeros((2,), jnp.int32)
    active = jnp.asarray([True, False])
    for step in range(4):
        d, c = 30 + step, 12 + step * C
        got, state, load = paged_forward_packed(
            params, CFG, jnp.asarray([tokens[0, d], 0]), cache,
            jnp.asarray(tokens[1:2, c:c + C]), jnp.asarray([1]),
            jnp.asarray([C]), active, window, wlen, use_kernel=kernels)
        adv = jnp.asarray([1, C], jnp.int32)
        if windowed:
            window, wlen = state, wlen + adv
            if step == 1:
                cache, wlen, _ = flush_paged_window(cache, window, wlen)
        else:
            cache = state._replace(lengths=cache.lengths + adv)
        assert worst(got[0], want[0, d]) < TOL, step
        assert worst(got[1], want[1, c + C - 1]) < TOL, step
        # 1 + C real rows x 3 of 8 experts, the mean over 4 layers
        touched, rows_max, rows_mean = np.asarray(load)
        assert 3 <= touched <= 8 and 1 <= rows_max <= 1 + C
        assert rows_mean == pytest.approx((1 + C) * 3 / 8)


# -- (e) the Mosaic paged kernel alone, interpreted -------------------------

@pytest.mark.parametrize("sw", [0, 5, 8, 64])
@pytest.mark.parametrize("staged", [0, 3])
@pytest.mark.parametrize("lens", [(3, 17, 30), (22, 0, 30)],
                         ids=["short_slot", "long_slots"])
def test_paged_kernel_slides(sw, staged, lens):
    """paged_attention with a sliding window against the dense softmax
    over the same keys: 14 queries over 2 KV heads (7 a head), pages of
    4. With a short slot the grid starts at page 0 and the long slots'
    dead pages are predicated off; with long slots only (one of them
    idle, which binds nothing) the grid starts past page 0; a slot's
    first live page is masked in part, and (staged) the bound falls
    inside the window segment of the short slot."""
    S, Nq, Kv, H, page, W = 3, 14, 2, 16, 4, 4
    lens = np.array(lens)
    ks = jax.random.split(jax.random.PRNGKey(sw + 7 * staged), 5)
    mp = 8
    pool_k = jax.random.normal(ks[0], (2, S * mp + 1, Kv, page, H))
    pool_v = jax.random.normal(ks[1], (2, S * mp + 1, Kv, page, H))
    table = jnp.asarray(np.arange(S * mp).reshape(S, mp), jnp.int32)
    q = jax.random.normal(ks[2], (S, Nq, H))
    kw = {}
    if staged:
        kw = dict(win_k=jax.random.normal(ks[3], (2, S, Kv, W, H)),
                  win_v=jax.random.normal(ks[4], (2, S, Kv, W, H)),
                  win_count=jnp.where(jnp.asarray(lens) > 0, staged, 0))
    got = paged_attention(q, pool_k, pool_v, 1, table,
                          jnp.asarray(lens, jnp.int32), sliding_window=sw,
                          interpret=True, **kw)
    for s in np.flatnonzero(lens):
        k = pool_k[1, table[s]].transpose(0, 2, 1, 3).reshape(mp * page, Kv, H)
        v = pool_v[1, table[s]].transpose(0, 2, 1, 3).reshape(mp * page, Kv, H)
        k, v = k[:lens[s]], v[:lens[s]]
        if staged:
            k = jnp.concatenate(
                [k, kw["win_k"][1, s, :, :staged].transpose(1, 0, 2)])
            v = jnp.concatenate(
                [v, kw["win_v"][1, s, :, :staged].transpose(1, 0, 2)])
        n = k.shape[0]
        if sw:
            k, v = k[max(0, n - sw):], v[max(0, n - sw):]
        k, v = jnp.repeat(k, Nq // Kv, axis=1), jnp.repeat(v, Nq // Kv, axis=1)
        p = jax.nn.softmax(jnp.einsum("nh,snh->ns", q[s], k) / 4.0, axis=-1)
        np.testing.assert_allclose(np.asarray(got[s]),
                                   np.asarray(jnp.einsum("ns,snh->nh", p, v)),
                                   rtol=2e-5, atol=2e-5)


# -- what the comparison can see --------------------------------------------

def test_router_read_after_attention_fails_the_comparison(
        params, tokens, want, monkeypatch):
    """The reference with its router on the feed-forward's input, as
    Mixtral's is (the planted fault: its expert layer re-reads the
    router from the input it is given), is another model: off by
    thousands of times the limit the program is held to."""
    experts = ref.experts
    monkeypatch.setattr(ref, "experts", lambda h, r, w, top_k: experts(
        h, h @ w["router"], w, top_k))
    # a function of its own, so that no earlier trace of the layer serves
    monkeypatch.setattr(ref, "_layer", jax.jit(
        lambda *a: ref.layer(*a), static_argnums=(2, 3, 4, 5, 6)))
    late = ref.logits(tokens[0], leaf_of(params), file_config(CFG))
    assert worst(late, want[0]) > 1000 * TOL
    # and the program, told so, follows it there
    cache = Model(CFG).init_cache(1, 64)
    got, _ = forward(params, CFG.replace(router_input="ffn"),
                     jnp.asarray(tokens[:1]), cache)
    assert worst(got[0], np.asarray(late)) < TOL


@pytest.mark.parametrize("fault", [
    dict(sliding_window_layout=[0, 0, 0, 0]),
    dict(rope_layout=[1, 1, 1, 1]),
    dict(rope_layout=[0, 0, 0, 0]),
], ids=["window_off", "rope_on_a_full_layer", "no_rope"])
def test_a_wrong_pattern_fails_the_comparison(params, tokens, want, fault):
    cfg = dict(file_config(CFG), **fault)
    assert worst(ref.logits(tokens[0], leaf_of(params), cfg),
                 want[0]) > 100 * TOL


def test_routing_identity():
    """A softmax over all the experts whose top k are renormalised
    (`moe_primary_router_apply_softmax` and `norm_topk_prob` of the
    source) is the softmax over the chosen k's logits, which is what
    route_tokens computes."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (3, 11, 64)) * 3
    gates, idx = route_tokens(None, None, 6, logits=logits)
    full = jax.nn.softmax(logits, axis=-1)
    top, idx2 = jax.lax.top_k(full, 6)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-5)


def test_expert_load_counts_real_rows():
    logits = jnp.asarray([[[9., 8, 0, 0], [9, 0, 8, 0], [0, 0, 8, 9]]])
    ok = jnp.asarray([[True, True, False]])
    touched, rows_max, rows_mean = np.asarray(expert_load(logits, 2, ok))
    assert (touched, rows_max, rows_mean) == (3, 2, 1.0)


def test_mask_lower_bound():
    pos = jnp.asarray([[5, 6]])
    assert np.asarray(make_mask(pos, 8, 3)).astype(int).tolist() == \
        [[[0, 0, 0, 1, 1, 1, 0, 0], [0, 0, 0, 0, 1, 1, 1, 0]]]
    np.testing.assert_array_equal(np.asarray(make_mask(pos, 8, 0)),
                                  np.asarray(make_mask(pos, 8)))


# -- the models that were here ----------------------------------------------

def test_a_model_without_a_pattern_carries_none():
    """At the new fields' defaults no program changes: the layer tree
    rides its scans as it is, and a pattern that changes nothing (every
    layer rotates, a window longer than the cache) gives the same
    logits to the bit, prefill and decode."""
    base = tiny("llama", dtype="float32", param_dtype="float32")
    assert base.layer_pattern() is None
    params = Model(base).init(jax.random.PRNGKey(2))
    assert layer_stack(params["layers"], base) is params["layers"]
    same = base.replace(sliding_window=4096,
                        sliding_window_layout=(1,) * base.num_layers,
                        rope_layout=(1,) * base.num_layers)
    assert "pattern" in layer_stack(params["layers"], same)
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 258, (2, 12)))
    outs = []
    for cfg in (base, same):
        cache = Model(cfg).init_cache(2, 32)
        a, cache = forward(params, cfg, toks[:, :8], cache)
        b, cache = forward(params, cfg, toks[:, 8:9], cache)
        outs.append((np.asarray(a), np.asarray(b)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


def test_preset_is_the_published_model():
    cfg = smallthinker_21b_a3b()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == \
        (52, 2560, 28, 4, 128, 768, 151936)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.act,
            cfg.router_input) == (64, 6, "relu", "attn")
    pat = cfg.layer_pattern()
    assert pat["sliding_window"][:5].tolist() == [0, 4096, 4096, 4096, 0]
    assert pat["rope"].tolist() == [0, 1, 1, 1] * 13
    # a layout read from JSON is a list: the config still hashes
    assert hash(ModelConfig(num_layers=2, sliding_window=4,
                            sliding_window_layout=[0, 1])) is not None
    with pytest.raises(ValueError, match="1 entries for 2 layers"):
        ModelConfig(num_layers=2, sliding_window=4, sliding_window_layout=[1])
    # which layers slide is always stated; experts over chips do not
    # carry a router read before attention
    with pytest.raises(ValueError, match="come together"):
        ModelConfig(num_layers=2, sliding_window=4)
    with pytest.raises(ValueError, match="come together"):
        ModelConfig(num_layers=2, sliding_window_layout=[0, 1])
    with pytest.raises(ValueError, match="expert parallelism"):
        CFG.replace(moe_impl="ep")


def test_paths_that_scan_slices_refuse_a_pattern():
    from butterfly_tpu.models.common import uniform_layers_only
    with pytest.raises(NotImplementedError, match="pipeline"):
        uniform_layers_only(CFG, "pipeline serving")
    uniform_layers_only(tiny("mixtral"), "pipeline serving")


# -- a checkpoint in the source's names ---------------------------------------

def test_checkpoint_in_the_source_s_names_loads_and_agrees(tmp_path):
    """A synthetic checkpoint directory in the names models/smallthinker.py
    expects (NOT checked against a real one: the sandbox has none), with
    the source's config keys, through config_from_hf_dir and
    load_checkpoint: the program's logits are the reference's over the
    same tensors read by name in the source's [out, in] layout."""
    import json
    from safetensors.numpy import save_file
    from butterfly_tpu.ckpt.load import config_from_hf_dir, load_checkpoint
    c = CFG
    D, Nq, Kv, H, F, E, V = (c.hidden_size, c.num_heads, c.num_kv_heads,
                             c.head_dim, c.intermediate_size, c.num_experts,
                             c.vocab_size)
    rng = np.random.RandomState(3)

    def w(*shape):
        return (rng.randn(*shape) * 0.05).astype(np.float32)

    sd = {"model.embed_tokens.weight": w(V, D),
          "model.norm.weight": 1 + w(D), "lm_head.weight": w(V, D)}
    for l in range(c.num_layers):
        p = f"model.layers.{l}."
        sd[p + "input_layernorm.weight"] = 1 + w(D)
        sd[p + "post_attention_layernorm.weight"] = 1 + w(D)
        sd[p + "self_attn.q_proj.weight"] = w(Nq * H, D)
        sd[p + "self_attn.k_proj.weight"] = w(Kv * H, D)
        sd[p + "self_attn.v_proj.weight"] = w(Kv * H, D)
        sd[p + "self_attn.o_proj.weight"] = w(D, Nq * H)
        sd[p + "block_sparse_moe.primary_router.weight"] = w(E, D)
        for e in range(E):
            q = p + f"block_sparse_moe.experts.{e}."
            sd[q + "gate.weight"], sd[q + "up.weight"] = w(F, D), w(F, D)
            sd[q + "down.weight"] = w(D, F)
    save_file(sd, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(dict(
        file_config(c), model_type="smallthinker", vocab_size=V,
        hidden_size=D, num_attention_heads=Nq, num_key_value_heads=Kv,
        head_dim=H, moe_ffn_hidden_size=F, moe_num_primary_experts=E,
        max_position_embeddings=c.max_seq_len, tie_word_embeddings=False)))

    cfg = config_from_hf_dir(str(tmp_path)).replace(
        dtype="float32", param_dtype="float32")
    assert cfg == c
    params = load_checkpoint(str(tmp_path), cfg)

    by_name = {"ln1/scale": "input_layernorm", "ln2/scale":
               "post_attention_layernorm", "attn/wq": "self_attn.q_proj",
               "attn/wk": "self_attn.k_proj", "attn/wv": "self_attn.v_proj",
               "attn/wo": "self_attn.o_proj",
               "moe/router": "block_sparse_moe.primary_router"}
    heads = {"attn/wq": (D, Nq, H), "attn/wk": (D, Kv, H),
             "attn/wv": (D, Kv, H), "attn/wo": (Nq, H, D)}

    def leaf(path, layer=None):
        """The reference's weights straight from the named tensors: a
        linear layer's is [out, in], so x @ W.T."""
        if path == "embed/tok":
            return jnp.asarray(sd["model.embed_tokens.weight"])
        if path == "final_norm/scale":
            return jnp.asarray(sd["model.norm.weight"])
        if path == "lm_head":
            return jnp.asarray(sd["lm_head.weight"].T)
        path = path[len("layers/"):]
        pre = f"model.layers.{layer}."
        if path in by_name:
            a = sd[pre + by_name[path] + ".weight"]
            a = a if a.ndim == 1 else a.T
            return jnp.asarray(a.reshape(heads.get(path, a.shape)))
        which = path[len("moe/w_"):]
        return jnp.asarray(np.stack([
            sd[pre + f"block_sparse_moe.experts.{e}.{which}.weight"].T
            for e in range(E)]))

    toks = [int(t) for t in rng.randint(1, V, size=T)]
    got, _ = forward(params, cfg, jnp.asarray([toks]),
                     Model(cfg).init_cache(1, 64))
    assert worst(got[0], ref.logits(toks, leaf, file_config(c))) < TOL


# -- tools/window_parity.py, rehearsed ---------------------------------------

def toy_file(**serve) -> dict:
    """A configuration file of the toy, with the source's key names."""
    return dict(
        file_config(CFG.replace(sliding_window=48)), name="toy-smallthinker",
        model_type="smallthinker", hidden_size=CFG.hidden_size,
        head_dim=CFG.head_dim, num_attention_heads=CFG.num_heads,
        num_key_value_heads=CFG.num_kv_heads, vocab_size=CFG.vocab_size,
        intermediate_size=CFG.intermediate_size, num_experts=CFG.num_experts,
        num_experts_per_tok=CFG.num_experts_per_tok,
        max_position_embeddings=128, tie_word_embeddings=False,
        torch_dtype="float32", reference="smallthinker_f32",
        model=dict(arch="smallthinker", act="relu", router_input="attn",
                   sliding_window=48,
                   sliding_window_layout=list(CFG.sliding_window_layout),
                   rope_layout=list(CFG.rope_layout)),
        serve=dict(quant="none", kv_quant="none", max_batch=2, max_seq=128,
                   page_size=4, decode_steps_per_tick=2,
                   prefill_inline_budget=8, **serve))


def test_window_parity_tool_separates_its_faults_on_the_toy():
    """The check of the chip (a stream longer than the window through
    the packed step, against the reference in blocks), at a toy's size
    on the CPU: the clean run agrees on both sides of the window, the
    window taken away shows only past it, RoPE on the full layers
    shows everywhere."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    import window_parity
    out = window_parity.check(toy_file(), toy=True, stream=120, decode=12)
    assert out["evidence"] == "cpu toy", out
    assert out["rows_before"] >= 1 and out["rows_after"] >= 12
    assert out["clean"]["after_max"] < 1e-5 > out["clean"]["before_max"]
    assert out["window_off"]["before_max"] < 1e-5
    assert out["window_off"]["after_median"] > out["limit"]
    # at the toy's width (64) queries and keys are small and rotation
    # moves the logits by a hundredth of their spread: far over float32's
    # noise, under the chip's LIMIT, so the toy is not `ok`
    assert 1e-3 < out["rope_everywhere"]["before_median"] < out["limit"]
    assert 1e-3 < out["rope_everywhere"]["after_median"] < out["limit"]
    assert not out["ok"]


def test_reference_copies_are_equal():
    """The benchmark carries its own copy of the plain reference (its
    files are laid over other checkouts); the two are one text."""
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    assert (root / "servebench/references/smallthinker_f32.py").read_text() \
        == (root / "butterfly_tpu/models/smallthinker_f32.py").read_text()


# -- through the scheduler: the server's own path -----------------------------

@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_served_tokens_are_the_reference_s_greedy_tokens(params, kernels):
    """Three requests through the continuous scheduler (mixed blocks,
    the write-combined window, with `kernels` the paged kernel
    interpreted), prompts admitted while others decode, contexts past
    the window of 8: every served token is the argmax of the
    reference's logits over the tokens before it. And the tick records
    carry what the routing asked of the experts."""
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    rt = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4,
                       decode_steps_per_tick=2, prefill_inline_budget=8)
    sched = Scheduler(ServingEngine(Model(CFG), params, rt,
                                    use_kernels=kernels), seed=0)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist() for n in (5, 21, 13)]
    reqs = [sched.submit(prompts[0], max_new_tokens=14)]
    for _ in range(2):
        sched.tick()
    reqs += [sched.submit(p, max_new_tokens=10) for p in prompts[1:]]
    sched.run_until_done()
    leaf, fc = leaf_of(params), file_config(CFG)
    for prompt, req in zip(prompts, reqs):
        seq = list(prompt)
        for tok in req.output:
            top = np.asarray(ref.logits(np.asarray(seq), leaf, fc)[-1])
            order = np.argsort(top)
            # a near-tie would be a coin toss between two float32 programs
            assert top[order[-1]] - top[order[-2]] > 1e-5
            assert tok == order[-1]
            seq.append(tok)
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["experts_touched"] is not None]
    assert ticks
    for t in ticks:
        assert 3 <= t["experts_touched"] <= 8
        assert t["expert_rows_max"] >= t["expert_rows_mean"] > 0
    # the gauges hold the newest drained block's
    assert 3 <= sched._g_experts_touched.value <= 8
    assert sched._g_expert_rows_max.value >= 1


def test_a_dense_model_s_ticks_carry_no_expert_load():
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny("llama", dtype="float32", param_dtype="float32")
    rt = RuntimeConfig(max_batch_size=2, max_seq_len=64, page_size=8)
    eng = ServingEngine(Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)), rt)
    sched = Scheduler(eng, seed=0)
    sched.submit([5, 7, 11], max_new_tokens=6)
    sched.run_until_done()
    assert eng.last_expert_load is None
    ticks = sched.ticklog.dump()["ticks"]
    assert ticks and all(t["experts_touched"] is None for t in ticks)
