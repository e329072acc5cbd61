"""Expert parallelism: GShard-style capacity-based MoE dispatch/combine.

TPU-native realization of the reference's MoE expert-parallel requirement
(BASELINE.json configs[3], Mixtral-8x7B over ICI; the reference itself has
no implementation — SURVEY.md §0). Two dispatch mechanisms:

* **Scatter + explicit `lax.all_to_all`** (the scalable path, prefill):
  tokens are sequence-sharded over the `expert` axis inside a shard_map;
  each device counting-sorts its local routing assignments into a
  per-destination send buffer [N, ne, C, D] (scatter by computed slot),
  one tiled all_to_all moves tokens to their experts' devices, the local
  experts run their SwiGLU, and the reverse all_to_all returns outputs
  for a gather+weighted combine. Memory is O(B·T·k) indices + the [E,C,D]
  buffers — never a [B,T,k,E,C] one-hot.

* **One-hot einsum dispatch** (fallback: decode steps and shapes the
  seq split doesn't divide): dispatch/combine as einsums with one-hot
  tensors that GSPMD lowers itself (Mesh-TF lineage). Fine at T==1;
  at long prefill lengths the [B,T,k,E,C] dispatch tensor dwarfs the
  activations, hence the path above (VERDICT r2 weak item 5).

Capacity: each expert processes at most C tokens per sequence (einsum
path) or per source shard (a2a path); overflow tokens are dropped (their
FFN contribution is zero, residual passes through — standard
Switch/GShard semantics). With cf large enough that nothing drops the
result equals the dense reference `models.common.moe_block` exactly —
that is the parity test. Inference-only: no load-balancing aux loss.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from butterfly_tpu.core.config import ModelConfig
from butterfly_tpu.models.common import ACTIVATIONS, Params
from butterfly_tpu.quant.int8 import qeinsum


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint iff a mesh with the spec's axes is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set()
    for part in spec:
        if part is None:
            continue
        names.update(part if isinstance(part, tuple) else (part,))
    if not names.issubset(set(mesh.axis_names)):
        return x
    return lax.with_sharding_constraint(x, spec)


def expert_capacity(cfg: ModelConfig, tokens_per_seq: int) -> int:
    """Per-sequence per-expert token slots."""
    c = math.ceil(cfg.moe_capacity_factor * cfg.num_experts_per_tok
                  * tokens_per_seq / cfg.num_experts)
    return max(1, min(c, cfg.num_experts_per_tok * tokens_per_seq))


def moe_block_ep(x: jax.Array, p: Params, cfg: ModelConfig,
                 capacity: Optional[int] = None) -> jax.Array:
    """Expert-parallel MoE FFN: dispatch -> expert SwiGLU -> combine.

    x: [B,T,D]. Experts' weight leaves p["w_*"]: [E,D,F]/[E,F,D] (one
    layer's slice — the layer scan strips the L dim). Returns [B,T,D].

    Routes through the scatter+all_to_all dispatch when a live mesh has
    an active `expert` axis that divides T (prefill); decode steps and
    non-dividing shapes fall back to the one-hot einsum dispatch.

    `capacity` is per-sequence-per-expert slots on both paths (the a2a
    path converts it to its pooled per-shard buffer size so the no-drop
    contract is path-independent). Under a DROPPING capacity the paths
    may drop different tokens: the einsum path budgets per sequence, the
    a2a path pools its shard's budget — same volume, different victims.
    """
    from butterfly_tpu.ops.flash_attention import _auto_axes
    mesh = jax.sharding.get_abstract_mesh()
    if (not mesh.empty
            and "expert" in _auto_axes(mesh)   # not Manual from an outer map
            and mesh.shape["expert"] > 1
            and x.shape[1] > 1                 # decode: einsum path is fine
            and x.shape[1] % mesh.shape["expert"] == 0
            and cfg.num_experts % mesh.shape["expert"] == 0):
        return _moe_ep_a2a(x, p, cfg, capacity)
    return _moe_ep_einsum(x, p, cfg, capacity)


def _moe_ep_einsum(x: jax.Array, p: Params, cfg: ModelConfig,
                   capacity: Optional[int] = None) -> jax.Array:
    """One-hot einsum dispatch (GSPMD lowers the resharding itself)."""
    B, T, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = capacity or expert_capacity(cfg, T)

    from butterfly_tpu.models.common import route_tokens
    gates, idx = route_tokens(x, p["router"], k)      # [B,T,k]

    # Slot assignment: expert e takes tokens in (t, k)-priority order.
    emask = jax.nn.one_hot(idx, E, dtype=jnp.int32)    # [B,T,k,E]
    flat = emask.reshape(B, T * k, E)
    pos = jnp.cumsum(flat, axis=1) - 1                 # position in expert
    pos = pos.reshape(B, T, k, E)
    keep = (pos < C) & (emask > 0)                     # overflow -> drop
    emask = emask.astype(jnp.float32)

    # dispatch[b,t,e,c] = 1 iff token (b,t) occupies slot c of expert e
    slot = jax.nn.one_hot(pos, C, dtype=jnp.float32)   # [B,T,k,E,C]
    dispatch = jnp.einsum("btke,btkec->btec",
                          keep.astype(jnp.float32) * emask, slot)
    combine = jnp.einsum("btk,btke,btkec->btec",
                         gates, keep.astype(jnp.float32) * emask, slot)

    # The all-to-all: tokens (data-sharded) -> expert-major layout.
    xin = jnp.einsum("btec,btd->ebcd", dispatch.astype(x.dtype), x)
    xin = _constrain(xin, P("expert", "data", None, None))

    act = ACTIVATIONS[cfg.act]
    g = qeinsum("ebcd,edf->ebcf", xin, p["w_gate"])
    u = qeinsum("ebcd,edf->ebcf", xin, p["w_up"])
    y = qeinsum("ebcf,efd->ebcd", act(g) * u, p["w_down"])
    y = _constrain(y, P("expert", "data", None, None))

    # Reverse all-to-all + weighted combine back to token-major layout.
    out = jnp.einsum("btec,ebcd->btd", combine.astype(y.dtype), y)
    return _constrain(out, P("data", None, None))


def _moe_ep_a2a(x: jax.Array, p: Params, cfg: ModelConfig,
                capacity: Optional[int] = None) -> jax.Array:
    """Scatter + explicit all_to_all dispatch (shard_map over `expert`).

    Tokens are sequence-sharded over the expert axis; each device
    counting-sorts its local (token, k) assignments into per-destination
    send slots and ONE tiled all_to_all moves activations to their
    experts' devices (reverse for outputs). Capacity C is per (source
    shard, expert) — with a no-drop cf this equals the einsum path and
    the dense reference exactly.
    """
    mesh = jax.sharding.get_abstract_mesh()   # live: moe_block_ep gates on it
    N = mesh.shape["expert"]
    B, T, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    ne = E // N                          # experts owned per shard
    Tl = T // N                          # local sequence chunk
    if capacity is not None:
        # The caller's `capacity` means per-sequence-per-expert (the
        # einsum path's unit). Pooled per-shard equivalent that keeps the
        # no-drop contract exact: B sequences x min(capacity, k*Tl)
        # worst-case assignments each (a sequence's hot tokens may all
        # land in one shard's chunk).
        C = min(capacity * B, k * B * Tl)
    else:
        C = expert_capacity(cfg, B * Tl)

    body = partial(_a2a_body, cfg=cfg, N=N, ne=ne, C=C)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "expert", None),
                  {"router": P(), "w_gate": P("expert"), "w_up": P("expert"),
                   "w_down": P("expert")}),
        out_specs=P(None, "expert", None),
        axis_names={"expert"}, check_vma=False)
    return fn(x, {kk: p[kk] for kk in
                  ("router", "w_gate", "w_up", "w_down")})


def _a2a_body(x, p, *, cfg: ModelConfig, N: int, ne: int, C: int):
    """Per-device half of the a2a dispatch (inside shard_map)."""
    B, Tl, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    A = B * Tl * k                                      # local assignments

    from butterfly_tpu.models.common import route_tokens
    gates, idx = route_tokens(x, p["router"], k)        # [B,Tl,k]

    # counting sort by expert: slot of assignment a within its expert
    g_flat = idx.reshape(A)                             # global expert ids
    onehot = jax.nn.one_hot(g_flat, E, dtype=jnp.int32)  # [A,E] (small)
    pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(A), g_flat]  # [A]
    keep = pos < C

    # scatter tokens into the send buffer [N, ne, C, D]; dropped/overflow
    # assignments get an out-of-range index (scatter mode drops them)
    dest = jnp.where(keep, g_flat * C + pos, N * ne * C)
    x_rep = jnp.repeat(x.reshape(B * Tl, D), k, axis=0)  # [A,D] per-assign
    send = jnp.zeros((N * ne * C, D), x.dtype).at[dest].set(
        x_rep, mode="drop").reshape(N, ne, C, D)

    # one tiled all_to_all each way; FFN runs expert-major in between
    recv = lax.all_to_all(send, "expert", 0, 0, tiled=True)  # [N,ne,C,D]
    xin = recv.transpose(1, 0, 2, 3).reshape(ne, N * C, D)
    act = ACTIVATIONS[cfg.act]
    gg = qeinsum("ecd,edf->ecf", xin, p["w_gate"])
    uu = qeinsum("ecd,edf->ecf", xin, p["w_up"])
    y = qeinsum("ecf,efd->ecd", act(gg) * uu, p["w_down"])
    y = y.reshape(ne, N, C, D).transpose(1, 0, 2, 3)
    y_back = lax.all_to_all(y, "expert", 0, 0, tiled=True)   # [N,ne,C,D]

    # gather each assignment's expert output and combine with its gate
    y_flat = jnp.take(y_back.reshape(N * ne * C, D), jnp.minimum(
        dest, N * ne * C - 1), axis=0)
    y_flat = jnp.where(keep[:, None], y_flat, 0.0).astype(x.dtype)
    out = y_flat.reshape(B, Tl, k, D) * gates[..., None].astype(x.dtype)
    return jnp.sum(out, axis=2)
