"""JoyAI-LLM-Flash's family on the CPU at a toy's size with every
mechanism present: the query's latent, one cached row of latent + rotary
key, value heads narrower than the query's, interleaved rotation, a
leading dense layer of its own width, sigmoid routing with a selection
bias and a scale, a shared expert, an untied head. LOGITS against the
plain float32 reference (servebench/references/joyai_f32.py), which
shares no code with the program and takes the EXPANDED form: the
absorbed read is never compared only with itself."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from butterfly_tpu.cache.paged import (
    LANES, flush_paged_window, init_kv_window, init_paged_cache,
    paged_forward, paged_forward_packed, paged_forward_window, pool_layout,
    pool_row)
from butterfly_tpu.core.config import (
    PRESETS, ModelConfig, RuntimeConfig, joyai_llm_flash, tiny)
from servebench.references import joyai_f32 as ref
from butterfly_tpu.models.common import (
    Model, expert_load, forward, init_cache, layer_runs, route_tokens)
from butterfly_tpu.quant.int8 import (
    init_params_by_leaf, is_quantized_leaf, quantize_int8)

forward = jax.jit(forward, static_argnums=(1,), static_argnames=("fresh",))
_packed_step = jax.jit(
    lambda params, cfg, *a, use_kernel=False: paged_forward_packed(
        params, cfg, *a, use_kernel=use_kernel),
    static_argnums=(1,), static_argnames=("use_kernel",))

CFG = tiny("joyai", dtype="float32", param_dtype="float32")
RT = RuntimeConfig(max_batch_size=3, max_seq_len=64, page_size=4)
T, C = 40, 6
#: rms difference over the standard deviation of the reference's logits
#: at the position. float32 on both sides on the CPU reads 1e-7 to 1e-6
#: (sums in another order: the absorbed products against the expanded);
#: a bfloat16 program reads 1e-2, a term left out 1e-1 and more
TOL = 2e-5


def file_config(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, as a configuration file
    of `cfg` would hold them."""
    return dict(
        rms_norm_eps=cfg.norm_eps, num_hidden_layers=cfg.num_layers,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_routed_experts=cfg.num_experts,
        first_k_dense_replace=cfg.first_k_dense,
        routed_scaling_factor=cfg.routed_scaling_factor)


def leaf_of(params):
    def leaf(path, layer=None):
        node = params
        for key in path.split("/"):
            node = node[key]
        if is_quantized_leaf(node):
            q8, s = node["q8"], node["s"]
            if layer is not None:
                q8, s = q8[layer], s[layer]
            return q8.astype(jnp.float32) * s.astype(jnp.float32)
        return (node if layer is None else node[layer]).astype(jnp.float32)
    return leaf


def seeded_params(cfg=CFG):
    p = Model(cfg).init(jax.random.PRNGKey(0))
    # norms that are not all ones, so that a norm put in the wrong place
    # shows; sublayers loud enough to move the stream off the embedding
    # and attention scores spread enough that a wrong rotation, scale or
    # mask moves the logits
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))

    def jitter(a):
        return 1 + 0.3 * jax.random.normal(next(keys), a.shape)

    at = p["layers"]["attn"]
    for g in (p["layers"]["ln1"], p["layers"]["ln2"], at["q_norm"],
              at["kv_norm"], p["final_norm"]):
        g["scale"] = jitter(g["scale"])
    at["wo"] = at["wo"] * 40
    at["w_uq"] = at["w_uq"] * 20
    at["w_uk"] = at["w_uk"] * 20
    at["w_dkv"] = at["w_dkv"] * 20
    p["dense"]["mlp"]["w_down"] = p["dense"]["mlp"]["w_down"] * 40
    p["sparse"]["moe"]["w_down"] = p["sparse"]["moe"]["w_down"] * 40
    p["sparse"]["shared"]["w_down"] = p["sparse"]["shared"]["w_down"] * 40
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params()


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(3).randint(1, CFG.vocab_size, (3, T))


def reference(params, tokens, cfg=CFG, **kw):
    return np.asarray(ref.logits(np.asarray(tokens), leaf_of(params),
                                 file_config(cfg), **kw))


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward of the three sequences: [3, T, V]."""
    return np.stack([reference(params, t) for t in tokens])


def err(got, want):
    """rms difference over the std of the reference's row."""
    d = np.asarray(got, np.float64) - want
    return float(np.sqrt(np.mean(d * d)) / np.std(want))


def test_the_reference_is_not_trivial(want, tokens):
    # rows differ by position, and the attention matters: a sequence
    # whose earlier tokens change moves a later row
    assert np.std(want) > 0.05
    assert err(want[0, 5], want[0, 20]) > 0.1


def test_the_reference_reads_its_context_and_its_rotation(params, tokens):
    """The reference's row at a position changes when an EARLIER token
    does, and when the same tokens stand one position later (the shared
    rotary key is rotated): neither the mask nor the rotation is dead
    in the toy's weights."""
    seq = tokens[0].copy()
    base = reference(params, seq)
    seq[3] = (seq[3] + 7) % CFG.vocab_size
    assert err(reference(params, seq)[30], base[30]) > 1e-3
    shifted = reference(params, np.concatenate([[5], tokens[0][:-1]]))
    assert err(shifted[31], base[30]) > 1e-3


# -- the contiguous cache -----------------------------------------------------

@pytest.mark.parametrize("fresh", [False, True], ids=["absorbed", "expanded"])
def test_contiguous_forward_whole(params, tokens, want, fresh):
    """One call over the whole sequence: absorbed over the rows it just
    wrote (the refcheck's prefill), or fresh and EXPANDED (the engine's
    prefill). Both equal the reference, so each other."""
    cache = init_cache(CFG, 3, 64)
    got, cache = forward(params, CFG, jnp.asarray(tokens), cache, fresh=fresh)
    for s in range(3):
        for pos in range(T):
            assert err(got[s, pos], want[s, pos]) < TOL, (s, pos)
    assert cache.v is None and cache.k.shape == (3, 3, 64, 1, CFG.latent_row)


def test_the_two_forms_agree_with_each_other(params, tokens):
    cache = init_cache(CFG, 3, 64)
    a, ca = forward(params, CFG, jnp.asarray(tokens), cache, fresh=False)
    b, cb = forward(params, CFG, jnp.asarray(tokens), cache, fresh=True)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * float(jnp.std(a)) * 10
    # and they cache the same rows
    np.testing.assert_allclose(np.asarray(ca.k), np.asarray(cb.k), rtol=1e-4,
                               atol=1e-4)


def test_prefill_then_decode_through_the_cache(params, tokens, want):
    """servebench/refcheck.py's drive: a prefill of 12, then decode
    calls of one token through the cache, each ABSORBED over the cached
    rows."""
    cache = init_cache(CFG, 3, 64)
    got, cache = forward(params, CFG, jnp.asarray(tokens[:, :12]), cache)
    assert err(got[1, -1], want[1, 11]) < TOL
    for j in range(12, 24):
        got, cache = forward(params, CFG, jnp.asarray(tokens[:, j:j + 1]),
                             cache)
        for s in range(3):
            assert err(got[s, 0], want[s, j]) < TOL, (s, j)
    assert int(cache.length[0]) == 24


def test_chunked_prefill_equals_one_shot(params, tokens, want):
    cache = init_cache(CFG, 3, 64)
    for lo in range(0, 21, 7):
        got, cache = forward(params, CFG, jnp.asarray(tokens[:, lo:lo + 7]),
                             cache)
        assert err(got[2, -1], want[2, lo + 6]) < TOL


# -- the pool: one row a token, no values ------------------------------------

def test_the_pool_holds_one_row_a_token_and_no_value_pool():
    cache = init_paged_cache(CFG, RT)
    heads, width = pool_row(CFG)
    assert (heads, width) == (1, LANES) and CFG.latent_row == 32 + 8 == 40
    assert cache.k_pages.shape == (3, 3 * 16 + 1, 1, 4, LANES)
    assert cache.v_pages is None and cache.k_scale_pages is None
    assert pool_layout(CFG) == "latent"
    win = init_kv_window(cache, 8)
    assert win.v is None and win.k.shape == (3, 3, 1, 8, LANES)
    # the published sizes: 576 values, 1,152 B, in five lane tiles
    big = joyai_llm_flash()
    assert big.latent_row == 576 and pool_row(big) == (1, 640)
    assert big.latent_row * 2 == 1152
    with pytest.raises(NotImplementedError, match="int8 KV cache"):
        init_paged_cache(CFG, dataclasses.replace(RT, kv_quant="int8"))


# -- the packed step ----------------------------------------------------------

class Packed:
    """What engine._packed_scan does around one packed step, by hand:
    three slots with a page-table row each, the KV window and its flush
    every third step."""

    def __init__(self, params, cfg=CFG, windowed=True, width=C,
                 use_kernel=False):
        self.params, self.cfg, self.C = params, cfg, width
        self.use_kernel = use_kernel
        cache = init_paged_cache(cfg, RT)
        S, mp = cache.page_table.shape
        self.cache = cache._replace(page_table=jnp.arange(
            S * mp, dtype=jnp.int32).reshape(S, mp))
        self.window = init_kv_window(self.cache, 3 * width) \
            if windowed else None
        self.wlen = jnp.zeros((S,), jnp.int32) if windowed else None
        self.steps, self.loads = 0, []

    def flush(self):
        if self.window is not None:
            self.cache, self.wlen, _ = flush_paged_window(
                self.cache, self.window, self.wlen)

    def restart(self, slot):
        self.flush()
        self.cache = self.cache._replace(
            lengths=self.cache.lengths.at[slot].set(0))

    def step(self, decode: dict, chunk=None):
        """decode {slot: token}; chunk (slot, tokens up to C) or None.
        Returns {slot: logits [V]} of the rows the head read."""
        S = self.cache.num_slots
        if self.steps % 3 == 0:
            self.flush()
        self.steps += 1
        toks, active = np.zeros((S,), np.int32), np.zeros((S,), bool)
        for s, t in decode.items():
            toks[s], active[s] = t, True
        ctok, cslot, count = np.zeros((1, self.C), np.int32), 0, 0
        if chunk is not None:
            cslot, count = chunk[0], len(chunk[1])
            ctok[0, :count] = chunk[1]
        logits, kv, load = _packed_step(
            self.params, self.cfg, jnp.asarray(toks), self.cache,
            jnp.asarray(ctok), jnp.asarray([cslot]), jnp.asarray([count]),
            jnp.asarray(active), self.window, self.wlen,
            use_kernel=self.use_kernel)
        adv = jnp.asarray(active, jnp.int32).at[cslot].add(count)
        if self.window is not None:
            self.window, self.wlen = kv, self.wlen + adv
        else:
            self.cache = kv._replace(lengths=self.cache.lengths + adv)
        self.loads.append(np.asarray(load))
        heads = dict(decode)
        if count:
            heads[cslot] = None
        return {s: np.asarray(logits[s]) for s in heads}


def scripted_run(params, tokens, windowed=True, cfg=CFG, use_kernel=False):
    """Slot 1 takes sequence 1's first 20 tokens in chunks of 6 (the
    last holds 2 and 4 of filler) and decodes to position 30 while slot
    0 takes sequence 0's first 15 (6, 6, 3) and decodes beside it; then
    slot 1's stream ends and the slot is given to sequence 2 from
    position 0 while slot 0 decodes on. Slot 2 never holds a stream.
    Returns ([(sequence, position, logits)], the driver, the decode
    rows' positions step by step)."""
    drv, out, read = Packed(params, cfg, windowed, use_kernel=use_kernel), \
        [], []
    at = {0: 0, 1: 0}
    seq = {0: 0, 1: 1}

    def feed(decode_slots, chunk_slot=None, n=0):
        decode = {s: tokens[seq[s], at[s]] for s in decode_slots}
        read.append(sum(at[s] + 1 for s in decode_slots))
        chunk = None if chunk_slot is None else (
            chunk_slot, tokens[seq[chunk_slot],
                               at[chunk_slot]:at[chunk_slot] + n])
        got = drv.step(decode, chunk)
        for s in decode_slots:
            at[s] += 1
        if chunk_slot is not None:
            at[chunk_slot] += n
        out.extend((seq[s], at[s] - 1, row) for s, row in got.items())

    for n in (6, 6, 6, 2):
        feed([], 1, n)
    for n in (6, 6, 3):
        feed([1], 0, n)
    while at[1] < 30:
        feed([0, 1])
    drv.restart(1)
    seq[1], at[1] = 2, 0
    for n in (6, 6, 5):
        feed([0], 1, n)
    for _ in range(4):
        feed([0, 1])
    return out, drv, read


@pytest.fixture(scope="module")
def scripted(params, tokens):
    return scripted_run(params, tokens)


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
def test_packed_steps_chunks_filler_decode_rows_and_a_reused_slot(
        params, tokens, want, windowed, scripted):
    """Chunks and decode rows in ONE step, through the window and its
    flush (and with the window off, straight into the pool): every row
    the head read is the reference's, a chunk's last column and a
    decode row alike, in a slot that held another stream before."""
    out, drv, read = scripted if windowed \
        else scripted_run(params, tokens, windowed)
    assert len(out) > 30
    assert {s for s, _, _ in out} == {0, 1, 2}
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)
    # the load: three of the experts', then the cached rows the step's
    # decode rows read, summed over the three layers: a row at position
    # p reads p + 1, a chunk's columns count nothing
    loads = np.stack(drv.loads)
    assert loads.shape[1] == 4
    np.testing.assert_array_equal(loads[:, 3], 3 * np.asarray(read))
    assert loads[:4, 3].sum() == 0 and loads[4, 3] == 3 * 21
    assert 0 < loads[:, 0].max() <= CFG.num_experts


@pytest.fixture
def chunk_of(monkeypatch):
    """Set ops/latent_attention.py's chunk to so many pages for one
    test: the traced programs that hold the module's own value are
    dropped before and after (a jitted call is keyed by its arguments,
    not by a constant of the module)."""
    import butterfly_tpu.ops.latent_attention as la

    def drop():
        la.latent_attention.__wrapped__.clear_cache()
        _packed_step.clear_cache()

    def set_to(pages):
        drop()
        monkeypatch.setattr(la, "PAGES_PER_CHUNK", pages)
    yield set_to
    drop()


def kernel_read_through_the_packed_run(params, tokens, want, cfg, pages,
                                       chunk_of):
    """Kernels on (interpreted on the CPU): the decode rows' read is the
    Pallas call over pages and the window, and every row is still the
    reference's; at a chunk of 2 pages of 4 rows a stream of 30 walks
    four chunks, the next slot's first among them."""
    from butterfly_tpu.ops import record_kernels
    chunk_of(pages)
    log = {}
    with record_kernels(log):
        out, _, _ = scripted_run(params, tokens, cfg=cfg, use_kernel=True)
    assert log.get("latent_win:interpret") and "dense_fallback" not in log
    for s, pos, row in out:
        assert err(row, want[s, pos]) < TOL, (s, pos)


@pytest.mark.parametrize("pages", [32, 2], ids=["one_chunk", "chunks_of_2"])
def test_the_kernel_read_is_the_jnp_read_through_the_packed_run(
        params, tokens, want, pages, chunk_of):
    kernel_read_through_the_packed_run(params, tokens, want, CFG, pages,
                                       chunk_of)


#: the kernel alone: name -> (pages a chunk, pages a slot's table holds,
#: each slot's flushed rows, each slot's staged rows). Pages of 16: at
#: the module's own chunk of 32 a length of 511, 512 and 513 ends a row
#: short of a chunk, on it and a row past it; at a chunk of 4 a stream
#: of 144 rows is two chunks and a page, and its neighbour's first chunk
#: is started beside the page.
KERNEL_ALONE = {
    "length_0": (32, 40, [0, 37, 0], [2, 8, 0]),
    "length_5": (32, 40, [5, 37, 5], [2, 8, 0]),
    "length_511": (32, 40, [511, 37, 511], [2, 8, 0]),
    "length_512": (32, 40, [512, 37, 512], [2, 8, 0]),
    "length_513": (32, 40, [513, 37, 513], [2, 8, 0]),
    "a_page_short_of_the_table": (32, 40, [624, 37, 624], [2, 8, 0]),
    "the_whole_table": (32, 40, [640, 37, 640], [2, 8, 0]),
    "dead_slot_first": (4, 9, [0, 37, 144, 64], [0, 3, 8, 1]),
    "dead_slot_last": (4, 9, [37, 144, 64, 0], [3, 8, 1, 0]),
    "three_dead_between_live": (4, 9, [144, 0, 0, 0, 37, 130],
                                [1, 0, 0, 0, 8, 3]),
    "every_slot_dead": (4, 9, [0, 0, 0], [0, 0, 0]),
    "window_rows_and_no_flushed_rows": (4, 9, [0, 0, 37, 0], [3, 8, 1, 5]),
    "contexts_ten_times_apart": (4, 88, [14, 1400, 140, 1399],
                                 [1, 2, 3, 4]),
    "a_chunk_of_one_page": (1, 9, [16, 0, 33, 144], [1, 1, 0, 8]),
}


@pytest.mark.parametrize("windowed", [False, True], ids=["pool", "window"])
@pytest.mark.parametrize("case", list(KERNEL_ALONE))
def test_the_kernel_alone_against_jnp_pages_window_and_dead_slots(
        case, windowed, chunk_of):
    """ops/latent_attention.py in interpret mode (KERNEL_ALONE), with
    and without the window, the first, the middle and the last layer;
    the pool and the window WHOLE and the layer's index, to the bit what
    the layer's slices alone give as a pool and a window of one. A slot
    with nothing to attend reads zeros."""
    import butterfly_tpu.ops.latent_attention as la
    pages, mp, lens, wc = KERNEL_ALONE[case]
    L, page, R, rank, Nq, S, W = 3, 16, 256, 128, 4, len(lens), 8
    P = S * mp + 1
    rs = np.random.RandomState(0)
    pool = jnp.asarray(rs.randn(L, P, 1, page, R), jnp.float32)
    q = jnp.asarray(rs.randn(S, Nq, R), jnp.float32)
    table = jnp.asarray(rs.permutation(P - 1).reshape(S, mp), jnp.int32)
    lens, wc = jnp.asarray(lens, jnp.int32), jnp.asarray(wc, jnp.int32)
    win = jnp.asarray(rs.randn(L, S, 1, W, R), jnp.float32)
    assert la.fits(pool, rank) and not la.fits(pool[:, :, :0], rank)
    chunk_of(pages)
    for layer in range(L):
        got = la.latent_attention(
            q, pool, layer, table, lens, *((win, wc) if windowed else ()),
            rank=rank, scale=0.1)
        alone = la.latent_attention(
            q, pool[layer][None], 0, table, lens,
            *((win[layer][None], wc) if windowed else ()),
            rank=rank, scale=0.1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
        rows = pool[layer][table][:, :, 0].reshape(S, mp * page, R)
        live = jnp.arange(mp * page)[None] < lens[:, None]
        if windowed:
            rows = jnp.concatenate([rows, win[layer, :, 0]], 1)
            live = jnp.concatenate(
                [live, jnp.arange(W)[None] < wc[:, None]], 1)
        s = jnp.einsum("snr,scr->snc", q, rows) * 0.1
        p = jax.nn.softmax(jnp.where(live[:, None], s, -1e30), -1) \
            * live[:, None]
        want = jnp.einsum("snc,scr->snr", p, rows[..., :rank])
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        dead = ~np.asarray(live).any(1)
        assert not np.asarray(got)[dead].any()      # nothing to attend
        assert np.asarray(got)[~dead].any(-1).all()


def _kernel_body(pages_per_chunk, monkeypatch):
    """The kernel body's jaxpr at a chunk of so many pages (the cells'
    32 heads and rows of 640 lanes, the window on)."""
    import butterfly_tpu.ops.latent_attention as la
    monkeypatch.setattr(la, "PAGES_PER_CHUNK", pages_per_chunk)
    S, Nq, R, page, P, W = 2, 32, 640, 16, 9, 8
    bf = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    jaxpr = jax.make_jaxpr(
        lambda *a: la.latent_attention.__wrapped__.__wrapped__(
            *a, rank=512, scale=0.1, interpret=True))(
        sds((S, Nq, R), bf), sds((1, P, 1, page, R), bf),
        sds((), jnp.int32), sds((S, 64), jnp.int32), sds((S,), jnp.int32),
        sds((1, S, 1, W, R), bf), sds((S,), jnp.int32))
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (S,)
    return call.params["jaxpr"]


def test_the_kernel_body_does_not_grow_with_the_chunk(monkeypatch):
    """Set-up by construction (ISSUE 50, as tests/test_kernels.py holds
    the paged body since PR 46): a chunk's copies are issued and awaited
    by a rolled loop over its live pages, so the body a serving program
    traces and lowers for each of its calls at every start is the same
    size at a chunk of 8 pages as at 32, and holds a group's starts
    twice (slot 0's own first chunk or a dead slot's hand-on; the next
    chunk's or the next slot's) and one wait, where the parent's held 64
    and 32 in 532 equations."""
    from butterfly_tpu.ops.latent_attention import GROUP_PAGES
    from test_kernels import _eqns
    bodies = {n: _kernel_body(n, monkeypatch) for n in (8, 32)}
    sizes = {n: sum(1 for _ in _eqns(b)) for n, b in bodies.items()}
    assert sizes[8] == sizes[32] < 450, sizes
    text = str(bodies[32])
    assert text.count("dma_start") == 2 * GROUP_PAGES < 64
    assert text.count("dma_wait") == 1


# -- the router ---------------------------------------------------------------

def test_the_bias_moves_the_choice_and_not_the_weights():
    logits = jnp.asarray([[[0.3, 0.1, 0.2, -0.4, 0.0, 0.25]]])
    bias = jnp.asarray([0.0, 0.5, 0.0, 0.0, 0.0, -0.5])
    s = np.asarray(jax.nn.sigmoid(logits))[0, 0]
    plain, idx0 = route_tokens(None, None, 2, logits, score="sigmoid",
                               scale=2.5)
    gates, idx = route_tokens(None, None, 2, logits, score="sigmoid",
                              bias=bias, scale=2.5)
    assert idx0[0, 0].tolist() == [0, 5] and idx[0, 0].tolist() == [1, 0]
    # the weights are the chosen experts' own scores, the bias nowhere
    np.testing.assert_allclose(np.asarray(gates)[0, 0],
                               2.5 * s[[1, 0]] / (s[1] + s[0]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(plain).sum(), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(), 2.5, rtol=1e-6)
    # expert_load counts the same choice
    ok = jnp.ones((1, 1), bool)
    assert np.asarray(expert_load(logits, 2, ok, "sigmoid", bias))[0] == 2


def test_equal_scores_go_to_the_lower_index_as_the_references_do():
    logits = jnp.zeros((1, 1, 6)).at[0, 0, 4].set(1.0)
    _, idx = route_tokens(None, None, 3, logits, score="sigmoid",
                          bias=jnp.zeros((6,)), scale=2.5)
    assert idx[0, 0].tolist() == [4, 0, 1]
    mix = ref.route(jnp.ones((1, 1)), logits[0], jnp.zeros((6,)), 3, 2.5)
    assert np.nonzero(np.asarray(mix)[0])[0].tolist() == [0, 1, 4]


def test_the_chosen_experts_are_the_references(params, tokens):
    """Layer 1's routing of real hidden rows: the program's 3 chosen
    experts and weights are the reference's mix, with the seeded bias
    in force (it moves some choice among these rows)."""
    lp = jax.tree.map(lambda a: a[0], params["sparse"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 64, CFG.hidden_size))
    gates, idx = route_tokens(h, lp["router"], 3, score="sigmoid",
                              bias=lp["router_bias"], scale=2.5)
    mix = np.asarray(ref.route(h[0], lp["router"], lp["router_bias"], 3, 2.5))
    got = np.zeros_like(mix)
    np.put_along_axis(got, np.asarray(idx[0]), np.asarray(gates[0]), axis=1)
    np.testing.assert_allclose(got, mix, rtol=1e-5, atol=1e-6)
    _, unbiased = route_tokens(h, lp["router"], 3, score="sigmoid",
                               scale=2.5)
    assert (np.sort(np.asarray(unbiased), -1)
            != np.sort(np.asarray(idx), -1)).any()


@pytest.mark.parametrize("k", [2, 6])
def test_a_softmax_family_routes_bit_for_bit_as_before(k):
    """The parent's route_tokens was lax.top_k then softmax over the
    chosen: the same values, to the bit, through the new signature."""
    logits = jax.random.normal(jax.random.PRNGKey(k), (3, 5, 16))
    gates, idx = route_tokens(None, None, k, logits)
    vals, want_idx = jax.lax.top_k(logits, k)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(gates) == np.asarray(jax.nn.softmax(vals, -1))).all()


def test_the_dense_layer_is_layer_zero_and_only_layer_zero(params):
    assert layer_runs(CFG) == [("attention", 0, 1, 0), ("attention", 1, 2, 1)]
    assert params["dense"]["mlp"]["w_gate"].shape == (1, 64, 96)
    assert params["sparse"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert params["sparse"]["moe"]["router_bias"].shape == (2, 8)
    assert params["sparse"]["shared"]["w_up"].shape == (2, 64, 32)
    assert "mlp" not in params["layers"] and "moe" not in params["layers"]
    assert set(params["layers"]) == {"ln1", "ln2", "attn"}
    # a model with no leading dense layer keeps one run and one stack
    assert layer_runs(tiny("mixtral")) == [("attention", 0, 2, 0)]


# -- weights ------------------------------------------------------------------

def test_weights_built_leaf_by_leaf_have_the_same_tree():
    cfg = CFG.replace(dtype="bfloat16")
    p = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    q = quantize_int8(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    assert jax.tree.structure(p) == jax.tree.structure(q)
    assert jax.tree.map(lambda a: a.shape, p) == \
        jax.tree.map(lambda a: a.shape, q)
    at = p["layers"]["attn"]
    for name in ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo"):
        assert is_quantized_leaf(at[name]), name
    assert is_quantized_leaf(p["dense"]["mlp"]["w_up"])
    assert is_quantized_leaf(p["sparse"]["moe"]["w_down"])
    assert is_quantized_leaf(p["sparse"]["shared"]["w_gate"])
    assert is_quantized_leaf(p["lm_head"])
    # the router, its bias, the norms and the embedding stay float
    for leaf in (p["sparse"]["moe"]["router"],
                 p["sparse"]["moe"]["router_bias"], at["kv_norm"]["scale"],
                 at["q_norm"]["scale"], p["embed"]["tok"]):
        assert leaf.dtype == jnp.bfloat16


def test_int8_weights_serve_the_reference_over_the_same_codes(tokens):
    """Weight-only int8: the reference reads the same codes times
    scales, so what is left is the program's arithmetic (the absorbed
    query dequantizes W_uk, whose scale runs along the contracted dim)."""
    p = quantize_int8(seeded_params(), CFG)
    want = reference(p, tokens[0])
    cache = init_cache(CFG, 1, 64)
    got, cache = forward(p, CFG, jnp.asarray(tokens[:1, :20]), cache)
    assert err(got[0, 19], want[19]) < TOL
    got, _ = forward(p, CFG, jnp.asarray(tokens[:1, 20:21]), cache)
    assert err(got[0, 0], want[20]) < TOL


# -- what cannot take the latent row refuses the model by name ---------------

def _engine(**rt):
    from butterfly_tpu.engine.serving import ServingEngine
    mesh = rt.pop("mesh", None)
    return ServingEngine(Model(CFG), seeded_params(), RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4, **rt), mesh=mesh)


def _mesh(axis):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:2]), (axis,))


def _fused_generate():
    from butterfly_tpu.models.common import decode_step_win
    decode_step_win(None, CFG, None, None, [], 0)


def _param_specs():
    from butterfly_tpu.parallel.partition import param_specs
    param_specs(CFG, _mesh("tensor"))


def _lane_wide(forward):
    """cache/paged.py's lane-wide forwards refuse the model before they
    read an argument: what still calls them (the speculative block's
    verify) does not carry what this model caches."""
    return forward(None, CFG, *[None] * 4)


REFUSALS = {
    "int8 KV cache": lambda: _engine(kv_quant="int8"),
    "int8 contiguous KV cache": lambda: init_cache(CFG, 1, 16, quant="int8"),
    "a device mesh \\(tensor=2\\)": lambda: _engine(mesh=_mesh("tensor")),
    "a device mesh \\(parallel/partition": _param_specs,
    "a device mesh \\(stage=2\\)": lambda: _engine(mesh=_mesh("stage")),
    "a device mesh \\(seq=2\\)": lambda: _engine(mesh=_mesh("seq")),
    "a device mesh \\(data=2\\)": lambda: _engine(mesh=_mesh("data")),
    "a device mesh \\(expert=2\\)": lambda: _engine(mesh=_mesh("expert")),
    "export": lambda: _engine().read_pages([0]),
    "import": lambda: _engine().write_pages([0], None, None),
    "host KV tier": lambda: _engine(prefix_caching=True, host_kv_tier_mb=1),
    "prefix caching": lambda: _engine(prefix_caching=True),
    "speculative": lambda: _engine(speculative_gamma=2),
    "paged_forward_window": lambda: _lane_wide(paged_forward_window),
    "lane-wide forward \\(paged_forward": lambda: _lane_wide(paged_forward),
    "write-combined fused generate": _fused_generate,
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_refused_by_name(what):
    with pytest.raises(NotImplementedError, match=what) as e:
        REFUSALS[what]()
    assert "latent" in str(e.value) and "kv_lora_rank 32" in str(e.value)


# -- through the scheduler: the server's own path -----------------------------

def served(params, prompts, new, cfg=CFG, together=False, **rt):
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    rt = RuntimeConfig(**{**dict(max_batch_size=2, max_seq_len=64,
                                 page_size=4, decode_steps_per_tick=2,
                                 prefill_inline_budget=8), **rt})
    sched = Scheduler(ServingEngine(Model(cfg), params, rt), seed=0)
    reqs = [sched.submit(prompts[0], max_new_tokens=new[0])]
    for _ in range(0 if together else 2):
        sched.tick()
    reqs += [sched.submit(p, max_new_tokens=n)
             for p, n in zip(prompts[1:], new[1:])]
    sched.run_until_done()
    return sched, reqs


def greedy_of_the_reference(params, prompt, output, cfg=CFG):
    """Every served token is the argmax of the reference's logits over
    the tokens before it, by a margin a rounding cannot close."""
    seq = list(prompt) + list(output)
    rows = reference(params, seq, cfg)
    for i, tok in enumerate(output):
        row = rows[len(prompt) + i - 1]
        order = np.argsort(row)
        assert row[order[-1]] - row[order[-2]] > 1e-4 * np.std(row), i
        assert tok == order[-1], i


def test_served_tokens_slot_reuse_and_a_recomputed_preemption(params,
                                                              monkeypatch):
    """Four requests over two slots through the continuous scheduler
    (mixed blocks, the lazy drain, the window and its flush), a pool of
    16 pages that the first two streams outgrow together: the younger
    is preempted MID-DECODE and resumed by recomputing its rows, both
    slots are reused after a finish, the pages all come back, and every
    served token is the reference's greedy token. The tick records
    count the latent rows the decode rows read."""
    from butterfly_tpu.sched.scheduler import Scheduler
    victims = []
    preempt = Scheduler._preempt
    monkeypatch.setattr(Scheduler, "_preempt", lambda self, req: (
        victims.append((req.state, len(req.output), len(req.prompt))),
        preempt(self, req))[1])
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, CFG.vocab_size, n).tolist()
               for n in (5, 6, 13, 9)]
    new = (40, 40, 10, 6)
    sched, reqs = served(params, prompts, new, together=True, num_pages=16,
                         prefill_inline_budget=4)
    for prompt, req, n in zip(prompts, reqs, new):
        assert len(req.output) == n
        greedy_of_the_reference(params, prompt, req.output)
    assert sched.alloc.free_pages == 16
    begun = [made for state, made, _ in victims if state == "running"]
    assert begun and max(begun) > 8
    assert int(sched.metrics()["preemptions_total"]) == len(victims)
    ticks = [t for t in sched.ticklog.dump()["ticks"]
             if t["latent_rows"] is not None]
    assert ticks and all(t["experts_touched"] is not None for t in ticks)
    assert all(t["ssm_rows"] is None for t in ticks)
    # a decode step at position p reads p + 1 rows in each of 3 layers:
    # every decode step of every request once (its first token comes
    # from its prompt's last chunk, and so does the token that follows
    # a recompute: that step's read is a chunk column's, not counted);
    # over that, at most the steps of the two blocks of two in flight
    # when a stream was preempted or finished, each over no more than
    # the 64 positions a slot holds
    least = 3 * sum(sum(range(len(p) + 1, len(p) + n))
                    for p, n in zip(prompts, new)) \
        - 3 * sum(made + plen for state, made, plen in victims
                  if state == "running")
    rows = sum(t["latent_rows"] for t in ticks)
    assert least <= rows <= least \
        + 3 * 64 * 4 * (len(victims) + len(reqs))
    assert all(t["latent_steps"] % 2 == 0 for t in ticks)
    assert sched.registry.snapshot()["latent_rows_read"] > 0


@pytest.mark.parametrize("arch", ["llama", "mixtral", "granite_hybrid"])
def test_a_model_without_latent_attention_counts_no_latent_rows(arch):
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    cfg = tiny(arch, dtype="float32")
    assert not cfg.is_latent and cfg.rope_dim == cfg.head_dim
    eng = ServingEngine(Model(cfg), Model(cfg).init(jax.random.PRNGKey(0)),
                        RuntimeConfig(max_batch_size=2, max_seq_len=64,
                                      page_size=4, decode_steps_per_tick=2))
    assert eng.cache.v_pages is not None
    sched = Scheduler(eng, seed=0)
    sched.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    sched.run_until_done()
    ticks = sched.ticklog.dump()["ticks"]
    assert ticks and all(t["latent_rows"] is None
                         and t["latent_steps"] is None for t in ticks)
    assert sched.registry.snapshot()["latent_rows_read"] == 0


def test_the_runtime_report_names_the_pool(params):
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler
    from butterfly_tpu.serve.server import runtime_report
    sched = Scheduler(ServingEngine(Model(CFG), params, RuntimeConfig(
        max_batch_size=2, max_seq_len=64, page_size=4)))
    rep = runtime_report(sched)
    assert rep["pool_layout"] == "latent" and rep["state"] is None


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_parity_tool_separates_its_faults_on_the_toy(dtype):
    """tools/latent_parity.py's check, rehearsed at the toy's size (a
    stream of 120 tokens, 24 of them decoded, through the packed step,
    the window and a flush every second step): the clean run under the
    limit in both groups, `pages_astray` over it in the decode rows and
    clean in the chunks' columns, `chunk_blind` over it everywhere."""
    import json
    from pathlib import Path

    import tools.latent_parity as lp
    config = json.loads((Path(__file__).parent / "servebench" / "files"
                         / "configs" / "tiny-joyai.json").read_text())
    config["torch_dtype"] = dtype
    out = lp.check(config, toy=True, stream=120, decode=24, past=64)
    assert out["evidence"] == "cpu toy" and out["ok"], out
    assert out["rows_decoded"] == 24 and out["rows_chunks"] >= 1
    clean, astray, blind = (out[f] for f in lp.FAULTS)
    assert max(clean["chunks_median"], clean["decoded_median"]) \
        < (1e-4 if dtype == "float32" else lp.LIMIT)
    assert astray["chunks_median"] == clean["chunks_median"]
    assert astray["decoded_median"] > lp.LIMIT
    assert min(blind["chunks_median"], blind["decoded_median"]) > lp.LIMIT


# -- the preset, the fields, the copies ---------------------------------------

def test_preset_is_the_published_model():
    cfg = PRESETS["joyai-llm-flash"]()
    assert cfg == joyai_llm_flash() and cfg.arch == "joyai"
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == \
        (40, 2048, 129280)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 1536, 128, 64, 128)
    assert cfg.qk_head_dim == 192 and cfg.rope_dim == 64
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.shared_intermediate_size, cfg.intermediate_size) == \
        (256, 8, 768, 768, 7168)
    assert cfg.first_k_dense == 1 and cfg.routed_scaling_factor == 2.5
    assert cfg.router_score == "sigmoid" and cfg.router_bias
    assert not cfg.tie_embeddings and cfg.rope_interleave
    # the benchmark's cut: the dense layer once, five expert layers
    cut = cfg.replace(num_layers=6)
    assert layer_runs(cut) == [("attention", 0, 1, 0), ("attention", 1, 5, 1)]
    # one attention layer's projections, as servebench/peaks.py counts
    shapes = jax.eval_shape(Model(cut).init, jax.random.PRNGKey(0))
    at = shapes["layers"]["attn"]
    assert sum(int(np.prod(at[n].shape[1:])) for n in (
        "w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "wo")) == 26_345_472


FIELD_ERRORS = [
    (dict(kv_lora_rank=32, qk_rope_head_dim=0), "without \\['qk_rope_head_dim'"),
    (dict(kv_lora_rank=0), "without kv_lora_rank"),
    (dict(qk_rope_head_dim=7), "rotates in pairs"),
    (dict(first_k_dense=3), "first_k_dense 3 of 3 layers"),
    (dict(num_experts=0), "router_score without num_experts"),
    (dict(router_score="tanh"), "unknown router_score"),
    (dict(sliding_window=8, sliding_window_layout=(1, 1, 1)),
     "sliding_window beside kv_lora_rank"),
    (dict(qk_norm=True), "qk_norm beside kv_lora_rank"),
    (dict(moe_impl="ep"), "does not carry"),
]


@pytest.mark.parametrize("kw, what", FIELD_ERRORS,
                         ids=[w[:24] for _, w in FIELD_ERRORS])
def test_the_new_fields_are_checked_together(kw, what):
    """A half-written "model" group fails where ModelConfig is built
    (servebench/launcher.py), naming the field, before anything else."""
    with pytest.raises(ValueError, match=what):
        tiny("joyai", **kw)


def test_older_families_reject_the_new_fields_they_cannot_carry():
    with pytest.raises(ValueError, match="routed_scaling_factor without"):
        tiny("llama", routed_scaling_factor=2.5)
    # leading dense layers ran as layer runs under latent attention alone
    # until PR 63 (Trinity: grouped-query attention behind them); what
    # still cannot stand beside them is refused by name
    assert tiny("mixtral", first_k_dense=1).first_k_dense == 1
    with pytest.raises(ValueError, match="first_k_dense beside layer_types"):
        tiny("granite_hybrid", first_k_dense=1)
    # an expert width of its own is carried by every model of experts
    cfg = tiny("mixtral", moe_intermediate_size=48)
    p = Model(cfg).init(jax.random.PRNGKey(0))
    assert p["layers"]["moe"]["w_gate"].shape == (2, 4, 64, 48)


# -- the chip's compiler, here ------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    env = pytest.MonkeyPatch()
    if "TPU_LOG_DIR" not in os.environ:
        env.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    env.undo()


@pytest.mark.parametrize("S, L, P, mp", [(96, 6, 49153, 512),
                                         (128, 10, 18433, 144)],
                         ids=["joyai48b.longthink", "xing29b.rollout"])
def test_the_read_compiles_for_the_chip_at_the_published_geometry(
        one_chip, monkeypatch, S, L, P, mp):
    """Mosaic takes the kernel at the cells' sizes (JoyAI's 96 slots over
    a pool of 49,153 pages in six layers and a table of 512; Xing's 128
    slots over 18,433 pages in ten and a table of 144; 32 heads, pages
    of 16 rows of 640 lanes, a window of 256) and the program holds no
    copy of the pool."""
    import butterfly_tpu.ops.latent_attention as la
    monkeypatch.setattr(la, "resolve_interpret", lambda i: False)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    Nq, Rp, W = 32, 640, 256
    bf = jnp.bfloat16
    fn = jax.jit(lambda q, pool, layer, t, n, w, wc:
                 la.latent_attention.__wrapped__(
                     q, pool, layer, t, n, w, wc, rank=512, scale=192 ** -0.5,
                     interpret=False))
    try:
        compiled = fn.lower(
            sds((S, Nq, Rp), bf), sds((L, P, 1, 16, Rp), bf),
            sds((), jnp.int32), sds((S, mp), jnp.int32),
            sds((S,), jnp.int32), sds((L, S, 1, W, Rp), bf),
            sds((S,), jnp.int32)).compile()
    except Exception as e:  # the TPU library is one process's at a time
        if "Mosaic" in str(e):
            raise
        pytest.skip(f"the TPU compiler could not be used here: {e}")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e6
    assert mem.argument_size_in_bytes > L * P * 16 * Rp * 2
