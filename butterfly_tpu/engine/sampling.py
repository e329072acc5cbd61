"""Token samplers: greedy, temperature, top-k, top-p.

Pure functions of (logits, key, params) so they live inside the jitted
decode step — no host round trip per token. All filtering is done with
static-shape sorts/masks (no dynamic shapes under jit, per XLA semantics).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0   # 0 => greedy
    top_k: int = 0             # 0 => disabled
    top_p: float = 1.0         # 1.0 => disabled
    max_new_tokens: int = 128
    stop_token: int = -1       # -1 => none

    @property
    def is_greedy(self) -> bool:
        return self.temperature == 0.0


def _apply_top_k(logits: jax.Array, k: int) -> jax.Array:
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _filter_logits(scaled: jax.Array, top_k: int, top_p: float) -> jax.Array:
    """The temperature-scaled logits after the static top-k/top-p
    filters — the distribution every sampling path (plain decode,
    speculative accept, residual resample) must agree on."""
    if top_k > 0:
        scaled = _apply_top_k(scaled, top_k)
    if top_p < 1.0:
        scaled = _apply_top_p(scaled, top_p)
    return scaled


@jax.named_scope("sample")
def speculative_accept(logits: jax.Array, drafts: jax.Array, key: jax.Array,
                       temps: jax.Array, top_k: int, top_p: float,
                       spec_mask: jax.Array = None,
                       q_logits: jax.Array = None):
    """Batched draft acceptance with the rejection-sampling correction
    (Leviathan et al. 2023), for one-hot OR real proposal
    distributions.

    logits [S, C, V] are a verify forward's per-position target logits
    (C = gamma + 1: position i is the next-token distribution after the
    i-th context token); drafts [S, gamma] the proposed tokens; temps
    [S] per-slot temperatures (0 = greedy row).

    Per position i the target distribution p_i is EXACTLY the one plain
    decode samples from (temperature-scaled, top-k/top-p filtered —
    _filter_logits). The proposal q_i:

    * q_logits None — DETERMINISTIC drafts (prompt lookup / greedy
      draft models): q is a point mass at the draft, and
      accept-with-prob min(1, p/q) reduces to accepting d_i with
      probability p_i(d_i); the first rejection resamples from the
      residual p_i with d_i masked out, renormalized.
    * q_logits [S, gamma, V] — REAL drafts (an on-device draft model,
      models/draft.py): the proposal logits the drafts were actually
      sampled from, ALREADY temperature-scaled and filtered exactly as
      the drafter sampled (the draft source passes its own
      _filter_logits output through, so p and q are scored on
      consistent supports). The full Leviathan rule applies: accept
      d_i w.p. min(1, p_i(d_i)/q_i(d_i)); the first rejection
      resamples from the normalized residual (p_i - q_i)+. Wherever a
      rejection can occur at all (p(d) < q(d)) the residual has mass
      — tokens with p > q exist because both distributions sum to 1 —
      so the degenerate empty-residual row is unreachable, the same
      argument as the one-hot case below.

    When every draft is accepted, one bonus token samples from
    p_gamma. Total emitted per slot: n_acc + 1 tokens whose joint law
    equals autoregressive sampling from p — speculation changes how
    many forwards the tokens take, never their distribution. Greedy
    rows (temp 0) take the `_accept_drafts` fast path semantics
    instead regardless of q: accept while d_i == argmax_i, emit the
    argmax at the first mismatch — output byte-identical to plain
    greedy decode (the draft-model parity contract rides on this).

    p_i(d_i) == 1 (the draft is the whole filtered nucleus) always
    accepts (u ~ U[0,1) < 1), so the degenerate all--inf residual row
    is never selected.

    `spec_mask` [S] bool (None = all true): rows with False ignore
    their drafts entirely — n_acc is forced to 0 AND the emitted token
    comes from the FULL distribution, not the residual (no accept test
    ran, so a residual resample would be biased away from the draft).
    This is the per-request speculation opt-out: such a slot emits one
    exact plain-decode sample per verify round.

    Returns (emitted [S, C], n_acc [S]): emitted[:, :n_acc] are the
    accepted drafts, emitted[:, n_acc] the correction/bonus sample;
    entries past n_acc are padding. Pure jax — usable inside a jitted
    scan (the serving spec block) or eagerly (generate_speculative).
    """
    S, C, V = logits.shape
    gamma = C - 1
    if spec_mask is None:
        spec_mask = jnp.ones((S,), bool)
    stochastic = (temps > 0)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, C]
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None, None]
    scaled = _filter_logits(logits / safe_t, top_k, top_p)
    ku, kr = jax.random.split(key)
    if gamma > 0:
        probs = jax.nn.softmax(scaled[:, :gamma, :], axis=-1)
        p_draft = jnp.take_along_axis(
            probs, drafts[..., None].astype(jnp.int32), axis=-1)[..., 0]
        u = jax.random.uniform(ku, (S, gamma))
        if q_logits is None:
            # one-hot proposal: accept w.p. p(d), residual = p with the
            # tested-and-rejected draft masked out
            acc_p = p_draft
            one_hot = jax.nn.one_hot(drafts, V, dtype=bool)
            resid = jnp.where(one_hot & spec_mask[:, None, None], -jnp.inf,
                              scaled[:, :gamma, :])
        else:
            # real proposal: accept w.p. min(1, p(d)/q(d)), residual =
            # normalized (p - q)+ (categorical renormalizes for us).
            # q(d) > 0 always — d was sampled from q — the guard only
            # shields padding rows from 0/0
            q_probs = jax.nn.softmax(q_logits, axis=-1)
            q_draft = jnp.take_along_axis(
                q_probs, drafts[..., None].astype(jnp.int32),
                axis=-1)[..., 0]
            acc_p = jnp.where(q_draft > 0, p_draft / q_draft, 1.0)
            resid_p = jnp.maximum(probs - q_probs, 0.0)
            resid = jnp.where(resid_p > 0, jnp.log(resid_p), -jnp.inf)
            # opt-out rows never tested: their distribution stays full
            resid = jnp.where(spec_mask[:, None, None], resid,
                              scaled[:, :gamma, :])
        accept = jnp.where(stochastic[:, None], u < acc_p,
                           drafts == greedy_tok[:, :gamma])
        accept = accept & spec_mask[:, None]
        n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                        axis=1).astype(jnp.int32)
        corr_logits = jnp.concatenate([resid, scaled[:, gamma:, :]], axis=1)
        pad_drafts = jnp.concatenate(
            [drafts.astype(jnp.int32), jnp.zeros((S, 1), jnp.int32)], axis=1)
    else:
        n_acc = jnp.zeros((S,), jnp.int32)
        corr_logits = scaled
        pad_drafts = jnp.zeros((S, C), jnp.int32)
    drawn = jax.random.categorical(kr, corr_logits, axis=-1).astype(jnp.int32)
    corr = jnp.where(stochastic[:, None], drawn, greedy_tok)
    emitted = jnp.where(jnp.arange(C)[None, :] < n_acc[:, None],
                        pad_drafts, corr)
    return emitted, n_acc


def tree_depth(width: int, nodes: int) -> int:
    """Expansion depth D of the budgeted token tree: `nodes` counts the
    root chain token plus D full fans of `width` siblings."""
    return (nodes - 1) // width


def tree_principal(d: int, width: int) -> int:
    """Chunk index of the depth-d principal node (sibling 0 of its fan;
    the root chain token at depth 0). The tree is a caterpillar: every
    depth-(d+1) fan hangs off the depth-d principal, so the principal
    chain IS the linear-gamma draft and siblings hedge each step."""
    return 0 if d == 0 else 1 + (d - 1) * width


def tree_node_index(d: int, j: int, width: int) -> int:
    """Chunk index of depth-d sibling j (d >= 1, 0 <= j < width)."""
    return 1 + (d - 1) * width + j


def tree_ancestor_matrix(width: int, nodes: int) -> np.ndarray:
    """[N, N] bool: anc[n, m] — may node n attend chunk position m?

    True for m on n's root->n ancestor path (self included). Host
    numpy, static under jit: this is the tree-attention mask's
    tree-local block, the structural difference between one verify
    forward over a token TREE and the causal chunk the linear spec
    scan dispatches."""
    N = nodes
    anc = np.zeros((N, N), dtype=bool)
    anc[0, 0] = True
    for d in range(1, tree_depth(width, nodes) + 1):
        path = [tree_principal(k, width) for k in range(d)]
        for j in range(width):
            n = tree_node_index(d, j, width)
            anc[n, path] = True
            anc[n, n] = True
    return anc


@jax.named_scope("sample")
def speculative_tree_accept(logits: jax.Array, drafts: jax.Array,
                            key: jax.Array, temps: jax.Array,
                            top_k: int, top_p: float,
                            spec_mask: jax.Array = None,
                            q_logits: jax.Array = None, *,
                            width: int, nodes: int):
    """Token-TREE draft acceptance (SpecInfer-style) with the
    recursive-residual rejection correction — the output law is exactly
    the target's, like `speculative_accept`, but the proposal is a
    width-w tree of i.i.d. candidates per depth instead of one chain.

    logits [S, N, V] are ONE tree-verify forward's per-node target
    logits (N = `nodes`, chunk layout `tree_node_index`: node 0 is the
    committed chain token, depth-d sibling j at 1 + (d-1)*w + j);
    drafts [S, D, w] the candidate fans (sibling 0 = the principal);
    q_logits [S, D, V] the drafter's filtered scaled logits each
    depth's fan was i.i.d.-sampled from (one shared q per fan — the
    i.i.d. property is what makes the recursive residual law below
    exact). Tree drafting requires real q, so q_logits is mandatory
    for stochastic rows (pass it; greedy rows ignore it).

    The accept walk runs root->leaf. At depth d the target p_d is the
    filtered distribution at the parent node (the depth-(d-1)
    principal); candidates are tested in sibling order against the
    recursive residual r_0 = p_d, accept candidate j w.p.
    min(1, r_j(x)/q(x)), on rejection r_{j+1} = norm((r_j - q)+)
    (token-independent, the multi-round speculative-sampling form of
    Leviathan rejection). First accepted sibling wins:

    * principal accepted and d < D — walk continues to depth d+1;
    * non-principal accepted (or d == D) — terminal: the final token
      samples from the FULL filtered target at the accepted node
      (its own next-token distribution, the bonus sample);
    * whole fan rejected — terminal: the final token samples from the
      last residual r_w at the parent.

    Greedy rows (temp 0) accept a sibling iff it IS the parent's raw
    argmax, and the final token is the argmax at the terminal node —
    byte-identical to plain greedy decode along the realized path.
    `spec_mask` opt-out rows run no accept test and emit one sample
    from the full filtered distribution at node 0, exactly like the
    linear path's opt-out.

    Returns (emitted [S, D+1], n_acc [S], perm [S, D+1]): emitted and
    n_acc follow the `speculative_accept` contract (accepted tokens
    then the correction/bonus, entries past n_acc padding). `perm` is
    the kept-KV chunk permutation — perm[:, 0] = 0 (the chain token),
    perm[:, i] = chunk index of the i-th accepted node — so the caller
    compacts the accepted path's K/V to the contiguous committed
    positions and the rejected branches die past the length, the
    rollback-exact-by-construction pattern one dimension wider.
    """
    S, N, V = logits.shape
    w, D = width, tree_depth(width, nodes)
    assert N == nodes and drafts.shape[1] == D and drafts.shape[2] == w
    C_out = D + 1
    if spec_mask is None:
        spec_mask = jnp.ones((S,), bool)
    stochastic = (temps > 0)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, N]
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None, None]
    scaled = _filter_logits(logits / safe_t, top_k, top_p)  # [S, N, V]
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (S, D, w))
    if q_logits is None:
        # greedy-only callers: a uniform stand-in keeps the stochastic
        # algebra well-defined; greedy rows never read it
        q_logits = jnp.zeros((S, D, V))
    q_probs = jax.nn.softmax(q_logits, axis=-1)  # [S, D, V]

    walking = spec_mask  # on the principal chain, not yet terminated
    n_acc = jnp.zeros((S,), jnp.int32)
    acc_stack = jnp.zeros((S, D), jnp.int32)
    perm = jnp.zeros((S, C_out), jnp.int32)  # perm[:, 0] = 0 = chain tok
    # terminal distribution: opt-out rows (never walking) keep the full
    # filtered target at node 0 — one exact plain-decode sample
    final_logits = scaled[:, 0, :]
    final_node = jnp.zeros((S,), jnp.int32)

    # D and w are tiny static ints: unrolled python loops, no scan
    for d in range(1, D + 1):
        pn = tree_principal(d - 1, w)
        p_d = jax.nn.softmax(scaled[:, pn, :], axis=-1)  # [S, V]
        q_d = q_probs[:, d - 1, :]
        r = p_d  # recursive residual, r_0 = p
        acc_here = jnp.zeros((S,), bool)
        tok_here = jnp.zeros((S,), jnp.int32)
        node_here = jnp.zeros((S,), jnp.int32)
        for j in range(w):
            tok = drafts[:, d - 1, j].astype(jnp.int32)
            r_tok = jnp.take_along_axis(r, tok[:, None], axis=1)[:, 0]
            q_tok = jnp.take_along_axis(q_d, tok[:, None], axis=1)[:, 0]
            # q(tok) > 0 always — tok was sampled from q — the guard
            # only shields greedy/padding rows from 0/0
            ratio = jnp.where(q_tok > 0, r_tok / q_tok, 1.0)
            acc_j = jnp.where(stochastic, u[:, d - 1, j] < ratio,
                              tok == greedy_tok[:, pn])
            take = walking & ~acc_here & acc_j
            tok_here = jnp.where(take, tok, tok_here)
            node_here = jnp.where(take, tree_node_index(d, j, w),
                                  node_here)
            acc_here = acc_here | take
            # residual update after a rejection — token-independent
            # (norm((r - q)+)), so one update serves every row still
            # rejecting; rows already accepted never read r again.
            # zero-mass residual (r == q exactly) is measure-zero for
            # real proposals; keep r to stay well-defined
            r_next = jnp.maximum(r - q_d, 0.0)
            mass = jnp.sum(r_next, axis=-1, keepdims=True)
            r = jnp.where(mass > 0, r_next / jnp.maximum(mass, 1e-38), r)
        acc_stack = acc_stack.at[:, d - 1].set(tok_here)
        perm = perm.at[:, d].set(jnp.where(acc_here, node_here, 0))
        n_acc = n_acc + acc_here.astype(jnp.int32)
        # fan fully rejected: final from the last residual (stochastic)
        # / the parent's argmax (greedy)
        rej = walking & ~acc_here
        resid = jnp.where(r > 0, jnp.log(r), -jnp.inf)
        final_logits = jnp.where(rej[:, None], resid, final_logits)
        final_node = jnp.where(rej, pn, final_node)
        # non-principal accepted (no children in the caterpillar) or
        # bottom of the tree: bonus from the accepted node's own
        # distribution
        term = acc_here & ((node_here != tree_principal(d, w))
                           if d < D else jnp.ones((S,), bool))
        term = walking & term
        node_scaled = jnp.take_along_axis(
            scaled, node_here[:, None, None], axis=1)[:, 0, :]
        final_logits = jnp.where(term[:, None], node_scaled, final_logits)
        final_node = jnp.where(term, node_here, final_node)
        walking = walking & acc_here & ~term

    drawn = jax.random.categorical(kr, final_logits, axis=-1)
    final_greedy = jnp.take_along_axis(
        greedy_tok, final_node[:, None], axis=1)[:, 0]
    final = jnp.where(stochastic, drawn.astype(jnp.int32), final_greedy)
    acc_pad = jnp.concatenate(
        [acc_stack, jnp.zeros((S, 1), jnp.int32)], axis=1)
    emitted = jnp.where(jnp.arange(C_out)[None, :] < n_acc[:, None],
                        acc_pad, final[:, None])
    return emitted, n_acc, perm


def _apply_top_p(logits: jax.Array, p: float) -> jax.Array:
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens until cumulative prob exceeds p (always keep the first)
    cutoff_mask = cum - probs > p
    cutoff = jnp.where(cutoff_mask, -jnp.inf, sorted_logits)
    threshold = jnp.min(jnp.where(jnp.isfinite(cutoff), cutoff, jnp.inf),
                        axis=-1, keepdims=True)
    return jnp.where(logits < threshold, -jnp.inf, logits)


def sample(logits: jax.Array, key: jax.Array, sp: SamplingParams) -> jax.Array:
    """logits [B,V] float32 -> token ids [B] int32. Branches are static
    (SamplingParams is a jit-static argument)."""
    if sp.is_greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / sp.temperature
    if sp.top_k > 0:
        logits = _apply_top_k(logits, sp.top_k)
    if sp.top_p < 1.0:
        logits = _apply_top_p(logits, sp.top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
