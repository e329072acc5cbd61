"""Int8 weight-only quantization for the bandwidth-bound decode path.

Decode throughput on TPU is HBM-bound: every step streams the full weight
tree (SURVEY.md §6; VERDICT.md round-1 roofline ~29% of v5e bandwidth).
Symmetric per-output-channel int8 halves the streamed bytes vs bfloat16.

Scheme: for each matmul weight W with contraction axes C,
    scale = absmax(W, over C) / 127        (keepdims, float32)
    q8    = round(W / scale)               (int8)
    W ~= q8 * scale

The forward NEVER computes `q8 * s` as a matmul operand: XLA fuses a
bare int8->bf16 convert into the dot's operand read, but an operand
*multiply* does not fold — it materializes the full dequantized tree in
HBM every step (measured on v5e: the 1B bench decode step streamed
~5.3GB instead of ~1.5GB, 26% roofline). Per-output-channel scales
commute with the contraction, so `qeinsum` computes
`einsum(x, q8.astype(bf16)) * s_out` — scale applied to the (tiny)
matmul OUTPUT — and only the int8 bytes ever cross HBM.

Quantized leaves are `{"q8": int8, "s": float32}` sub-dicts replacing the
original array; everything numerically delicate (embeddings, norms,
biases, MoE router) stays in the master dtype.
"""
from __future__ import annotations

from functools import partial as _partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def is_quantized_leaf(x: Any) -> bool:
    return isinstance(x, dict) and "q8" in x and "s" in x


def tree_is_quantized(params: Params) -> bool:
    """True if any leaf of the pytree is a `{"q8","s"}` quantized dict."""
    found = []
    jax.tree.map(lambda x: found.append(True) if is_quantized_leaf(x)
                 else None, params, is_leaf=is_quantized_leaf)
    return bool(found)


def maybe_dequant(w: Any, dtype) -> jax.Array:
    """Dequantize a `{"q8","s"}` leaf to `dtype`; pass arrays through.

    NB: using this as a matmul operand materializes the dequantized
    array (the scale multiply doesn't fold into the dot) — matmul call
    sites must use `qeinsum` instead; this exists for non-matmul uses
    and debugging.
    """
    if is_quantized_leaf(w):
        return w["q8"].astype(dtype) * w["s"].astype(dtype)
    return w


def qeinsum(spec: str, x: jax.Array, w: Any,
            dtype: Optional[Any] = None) -> jax.Array:
    """einsum(spec, x, W) for a possibly-quantized operand W. W stands
    second nearly everywhere; it may stand FIRST (qeinsum("edf,btd->ebtf",
    W, x): the same sum, the operands handed to the product in that
    order; models/common.py moe_block says where the order matters).

    Quantized: contracts x against the raw int8 codes (the int8->dtype
    convert fuses into the dot's operand read — only int8 bytes stream
    from HBM) and applies the per-output-channel scale to the OUTPUT.
    Valid because the scale has size-1 contraction dims (keepdims), so
    it commutes with the contraction: x @ (q8*s) == (x @ q8) * s. The
    output-shaped scale is derived by running the same einsum spec over
    an all-ones x surrogate (every dim 1) and the scale — shape algebra
    only; it broadcasts over the batch dims of the real output.
    """
    ops = [x, w]
    dtype = dtype or next(o for o in ops if not is_quantized_leaf(o)).dtype
    ones = None
    for i, o in enumerate(ops):
        if is_quantized_leaf(o):
            ops[i] = o["q8"].astype(dtype)
            ones = [jnp.ones((1,) * t.ndim, dtype) for t in ops]
            ones[i] = o["s"].astype(dtype)
        elif jnp.issubdtype(o.dtype, jnp.floating) and o.dtype != dtype:
            ops[i] = o.astype(dtype)  # master-dtype leaves compute in `dtype`
    y = jnp.einsum(spec, *ops)
    return y if ones is None else y * jnp.einsum(spec, *ones)


def _quant(w: jax.Array, axes: Tuple[int, ...], dtype) -> Dict[str, jax.Array]:
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q8 = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    # Scale lives in the compute dtype so engine cast_params is a no-op
    # on a quantized tree (no donating cast; the tree stays reusable).
    return {"q8": q8, "s": scale.astype(dtype)}


def quantize_int8(params: Params, cfg) -> Params:
    """Quantize every matmul weight of an init_params-shaped tree.

    Contraction axes per leaf (leading L = stacked layers):
      wq/wk/wv [L,D,N,H] -> D;  wo [L,N,H,D] -> (N,H); the output
        gate's wg [L,D,N,H] (cfg.attn_gate) as wq
      mlp w_gate/w_up [L,D,F] -> D;  w_down [L,F,D] -> F
      moe w_* [L,E,D,F] / [L,E,F,D] -> the D/F contraction axis
      shared w_* [L,D,F] / [L,F,D] -> as the dense mlp's
      mamba in_proj [Lm,D,P] -> D;  out_proj [Lm,Di,D] -> Di (the
        conv, A_log, D, dt_bias and the norm stay float)
      gdn in_proj [Ls,D,q|k|v|z] -> D;  out_proj [Ls,H dv,D] -> H dv: a
        Gated DeltaNet mixer's five wide projections (ab_proj, two
        numbers a head, the conv, A_log, dt_bias and the norm stay
        float)
      mamba1 in_proj [Lm,D,u|z] -> D;  out_proj [Lm,Di,D] -> Di: a
        Mamba-1 mixer's two wide projections (x_proj, dt_proj, the conv,
        A_log, D, dt_bias and the three inner norms stay float: what
        they give is exponentiated, and they are a fortieth of a mixer)
      latent attention: w_dq [L,D,Rq], w_uq [L,Rq,N,H], w_dkv [L,D,R+r],
        w_uk / w_uv [L,R,N,H] -> the dim behind L (w_uk is contracted
        over H in the absorbed read, which dequantizes it first:
        models/common.py latent_queries)
      lm_head [D,V] -> D; a tied head gets one from the embedding
        (tied_head)
      the residual streams' mixing (hc1 / hc2: phi [L,nD,n(2+n)], b,
        alpha) stays float, as a router does: its product is float32
        (models/common.py stream_read) and a thousandth of a layer
    The leaf's PATH decides (_contraction_axes), wherever its stack
    lies: under params["layers"], or top-level for a model whose layers
    run as runs (params["attn"], "mamba", "gdn", "mamba1", "dense",
    "sparse").
    Runs as one jit so a large tree quantizes device-side in one program.
    """

    dt = jnp.dtype(cfg.dtype)

    def leaf(path, w):
        axes = _contraction_axes(_path_names(path))
        return w if axes is None else _quant(w, axes, dt)

    @jax.jit
    def go(params):
        out = jax.tree_util.tree_map_with_path(leaf, params)
        if "lm_head" not in params and cfg.tie_embeddings:
            out["lm_head"] = tied_head(params["embed"]["tok"], dt)
        return out

    return go(params)


def tied_head(tok: jax.Array, dt) -> Dict[str, jax.Array]:
    """The [D, V] head of an embedding tok [V, D], quantized over D: a
    tied output head is held a SECOND time, as int8 codes. The
    embedding stays float (a lookup of a few rows), and the head, which
    a decode step streams whole, reads half the bytes."""
    return _quant(tok.T, (0,), dt)


def _path_names(path) -> list:
    return [getattr(p, "key", getattr(p, "name", "")) for p in path]


def _contraction_axes(path_names) -> Optional[Tuple[int, ...]]:
    """quantize_int8's contraction axes for the leaf at this tree path,
    or None for a leaf that stays float (embeddings, norms, biases,
    the MoE router)."""
    name = path_names[-1]
    parent = path_names[-2] if len(path_names) > 1 else ""
    if name in ("wq", "wk", "wv", "wg", "w_dq", "w_uq", "w_dkv", "w_uk",
                "w_uv"):
        return (1,)
    if name == "wo":
        return (1, 2)
    if parent == "moe" and name in ("w_gate", "w_up", "w_down"):
        return (2,)
    if parent in ("mlp", "shared") and name in ("w_gate", "w_up", "w_down"):
        return (1,)
    if parent in ("mamba", "gdn", "mamba1") \
            and name in ("in_proj", "out_proj"):
        return (1,)
    if name == "lm_head":
        return (0,)
    return None


def _leaf_kind(names, stream, post=1.0):
    """How init_params seeds the leaf at this tree path: a norm's scale
    "ones", a bias "zeros", a weight "normal" (N(0, .02)); of the
    residual streams' mixing (hc1 / hc2), b "normal_1" (N(0, 1)) and
    alpha "hundredths" (the constant .01: init_params says why); of a
    Mamba-1 mixer the taps, the skip, the rates and the steps as
    models.common.MAMBA1_SEEDS draws them.
    `stream`: models.common.stream_seed's pair, where a model seeds the
    embedding at 1 ("normal_1") and its sublayers' norms at a constant
    (a kind that is a number is that constant); `post`: the constant of
    the norms BEHIND the sublayers of a cfg.sandwich_norm model."""
    emb_std, ln = stream
    if list(names) == ["embed", "tok"] and emb_std == 1.0:
        return "normal_1"
    if names[-1] == "scale":
        if names[-2] in ("ln1_post", "ln2_post"):
            return post
        return ln if names[-2] in ("ln1", "ln2") and ln != 1.0 else "ones"
    if len(names) > 1 and names[-2].startswith("hc"):
        return {"b": "normal_1", "alpha": "hundredths"}.get(names[-1],
                                                            "normal")
    if len(names) > 1 and names[-2] == "mamba1":
        from butterfly_tpu.models.common import MAMBA1_SEEDS
        return MAMBA1_SEEDS.get(names[-1], "normal")
    return "zeros" if names[-1].startswith("b") else "normal"


#: what a leaf that is not drawn is filled with, by kind
_FILLS = {"ones": 1.0, "zeros": 0.0, "hundredths": 0.01}


def _leaf_values(k, *, shape, kind, axes, dt):
    """One leaf in its final form: a constant (_FILLS or a number), or
    drawn by kind (models.common.drawn: N(0, .02) for "normal") and
    quantized over `axes` when given, else cast to the compute dtype."""
    from butterfly_tpu.models.common import drawn
    if kind in _FILLS or not isinstance(kind, str):
        return jnp.full(shape, _FILLS.get(kind, kind), dt)
    w = drawn(k, shape, kind)
    return _quant(w, axes, dt) if axes is not None else w.astype(dt)


#: Per-program element budget for random init: the RNG's bit buffers and
#: the f32 intermediate are ~3x the leaf, so one 525M-element vocab leaf
#: (8B lm_head/embed) spikes ~6 GB — chunking bounds the transient.
_INIT_CHUNK_ELEMS = 128 * 2**20


def _chunk_plan(shape, axes, shard_factor):
    """(axis, chunk length) to build a large random leaf in pieces, or
    None when one program is within budget. Chunks run along a
    non-contracted axis (per-output-channel scales make them exactly
    independent), preferring one the mesh does not shard — joining
    chunks along a sharded axis costs a reshard — and a chunk along a
    sharded axis keeps a multiple of its shard count."""
    size = 1
    for n in shape:
        size *= n
    nchunks = -(-size // _INIT_CHUNK_ELEMS)
    cand = [d for d in range(len(shape)) if d not in (axes or ())]
    if nchunks <= 1 or not cand:
        return None
    ax = max(cand, key=lambda d: (shard_factor[d] == 1, shape[d]))
    n, f = shape[ax], shard_factor[ax]
    clen = -(-n // min(n, nchunks))
    clen = -(-clen // f) * f
    return (ax, clen) if clen < n else None


def init_params_by_leaf(cfg, key: jax.Array, quant: str = "none",
                        mesh=None) -> Params:
    """Random-init a weight tree one leaf at a time, each leaf born in
    the form the engines hold it in: int8 codes + scales for the matmul
    weights when quant="int8", the compute dtype for everything else,
    and — under a mesh — already in its partitioned layout
    (parallel/partition.py param_specs). Neither the master-dtype float
    tree nor an unsharded copy ever exists.

    `init_params` + `quantize_int8` as two device programs peaks at the
    full master-dtype tree (8B f32 = 32 GB — double a v5e chip's HBM);
    fusing them into one jit does NOT help — XLA schedules the cheap
    RNG ops ahead of the quantizations and materializes the float tree
    anyway (measured: the fused program ResourceExhausted a v5e). So
    each leaf is its own small program: peak = finished tree + one
    float chunk. Leaf roles (matmul -> quantize with quantize_int8's
    contraction axes; norm-scales -> ones; biases -> zeros; everything
    else -> N(0, .02)) are resolved by path over init_params'
    eval_shape tree, so the structure can't drift from the real
    initializer. This is the no-checkpoint path of the CLI
    and the tools (real deployments load checkpoints via ckpt/)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from butterfly_tpu.models.common import init_params, stream_seed

    if quant not in ("none", "int8"):
        raise ValueError(f"unknown weight quant {quant!r}")
    stream = stream_seed(cfg)
    dt = jnp.dtype(cfg.dtype)
    shapes = jax.eval_shape(_partial(init_params, cfg),
                            jax.ShapeDtypeStruct(key.shape, key.dtype))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [None] * len(leaves)
    if mesh is not None:
        from butterfly_tpu.parallel.partition import param_specs
        specs = jax.tree.leaves(param_specs(cfg, mesh),
                                is_leaf=lambda x: isinstance(x, P))
        if len(specs) != len(leaves):
            raise ValueError("param_specs does not mirror init_params")
    keys = jax.random.split(key, len(leaves))
    out = []
    progs = {}  # one jit per output layout: same-shaped leaves share it
    for (path, sd), k, spec in zip(leaves, keys, specs):
        names = _path_names(path)
        axes = _contraction_axes(names) if quant == "int8" else None
        kind = _leaf_kind(names, stream, (2 * cfg.num_layers) ** -0.5)
        sharding, factor = None, (1,) * len(sd.shape)
        if mesh is not None:
            dims = tuple(spec) + (None,) * (len(sd.shape) - len(spec))
            factor = tuple(
                int(np.prod([mesh.shape[a] for a in
                             (e if isinstance(e, tuple) else (e,))]))
                if e is not None else 1 for e in dims)
            sharding = NamedSharding(mesh, spec)
            if axes is not None:
                # the scale keeps the weight's spec except on its
                # contraction dims, which keepdims collapsed to 1
                sharding = {"q8": sharding, "s": NamedSharding(mesh, P(*[
                    None if i in axes else e for i, e in enumerate(dims)]))}
        prog = progs.get((spec, axes))
        if prog is None:
            prog = progs[spec, axes] = jax.jit(
                _leaf_values, out_shardings=sharding,
                static_argnames=("shape", "kind", "axes", "dt"))
        plan = _chunk_plan(sd.shape, axes, factor) \
            if str(kind).startswith("normal") else None
        if plan is None:
            out.append(prog(k, shape=sd.shape, kind=kind, axes=axes, dt=dt))
            continue
        ax, clen = plan
        starts = range(0, sd.shape[ax], clen)
        parts = [prog(ck, shape=tuple(
                     min(clen, n - lo) if d == ax else n
                     for d, n in enumerate(sd.shape)),
                      kind=kind, axes=axes, dt=dt)
                 for ck, lo in zip(jax.random.split(k, len(starts)), starts)]
        join = jax.jit(
            lambda *ps: jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=ax), *ps),
            out_shardings=sharding)
        out.append(join(*parts))
    params = jax.tree_util.tree_unflatten(treedef, out)
    if quant == "int8" and cfg.tie_embeddings:
        params["lm_head"] = jax.jit(tied_head, static_argnums=(1,))(
            params["embed"]["tok"], dt)
    return params


def quant_specs_like(qparams: Params, specs: Params) -> Params:
    """Mirror a param_specs tree onto a quantized tree.

    The weight's PartitionSpec applies to q8 unchanged; the scale keeps
    the spec only on dims that are still >1 (contraction dims collapsed
    to 1 by keepdims must not be sharded).
    """
    from jax.sharding import PartitionSpec as P
    if "lm_head" in qparams and "lm_head" not in specs:
        # a tied head's second copy (tied_head): the embedding's
        # [V, D] layout turned to [D, V]
        specs = {**specs, "lm_head": P(*specs["embed"]["tok"][::-1])}

    def rec(qp, sp):
        if is_quantized_leaf(qp):
            s_spec = P(*[sp[i] if qp["s"].shape[i] > 1 else None
                         for i in range(len(qp["s"].shape))])
            return {"q8": sp, "s": s_spec}
        if isinstance(qp, dict):
            return {k: rec(qp[k], sp[k]) for k in qp}
        return sp

    return rec(qparams, specs)


def shard_quantized_params(qparams: Params, cfg, mesh) -> Params:
    """device_put a quantized tree to its partitioned layout (TP etc.)."""
    from butterfly_tpu.parallel.partition import param_specs, to_shardings
    specs = quant_specs_like(qparams, param_specs(cfg, mesh))
    return jax.device_put(qparams, to_shardings(specs, mesh))
