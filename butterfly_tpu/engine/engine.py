"""Inference engine: jit-compiled prefill and decode steps + generate loops.

Realizes the reference's planned "Distributed Inference Engine"
(/root/reference/CLAUDE.md:19) the TPU way:

* One compiled prefill program (full-prompt forward, cache write) and one
  compiled decode program (single-token step). Both donate the KV cache so
  XLA updates it in place in HBM.
* A fused generate path (`lax.scan` over decode steps inside one jit) keeps
  the whole token loop device-resident — zero host round trips per token —
  which is what the tokens/sec/chip metric (BASELINE.json) rewards.
* Batch shapes are static: variable-length prompts are right-padded; padded
  key slots sit at positions the causal mask can never reach (a query at
  position p attends only j <= p, and pads land at j >= true_len > p), and
  decode overwrites them before they ever become visible.
"""
from __future__ import annotations

import contextlib
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
from butterfly_tpu.core.mesh import mesh_ctx
from butterfly_tpu.ops import kernel_mode, kernels_default, record_kernels
from butterfly_tpu.engine.sampling import SamplingParams, sample
from butterfly_tpu.models.common import KVCache, Model, forward, init_cache


@dataclass
class GenerateResult:
    tokens: np.ndarray          # [B, max_new] generated ids (post-stop garbage masked to pad)
    lengths: np.ndarray         # [B] number of valid generated tokens
    prompt_lengths: np.ndarray  # [B]


@dataclass
class SpeculativeResult:
    tokens: np.ndarray       # [n] generated ids (stop-truncated)
    forwards: int            # device forwards taken (prefill + verifies)
    accepted_drafts: int     # draft tokens accepted across all verifies

    @property
    def tokens_per_forward(self) -> float:
        return len(self.tokens) / max(1, self.forwards)


def _accept_drafts(draft, greedy) -> List[int]:
    """Greedy draft acceptance — the host fast path of the shared
    semantics sampling.speculative_accept implements on device for the
    serving spec block (the two must not drift; the temp-0 rows of the
    device kernel reproduce exactly this): emit greedy[0] (the token
    after `cur`), then keep accepting while draft[i] == greedy[i], each
    acceptance also emitting greedy[i+1]. Token-for-token identical to
    plain greedy decode by construction."""
    emitted = [int(greedy[0])]
    for i, d in enumerate(draft):
        if d != int(greedy[i]):
            break
        emitted.append(int(greedy[i + 1]))
    return emitted


def _ngram_draft(history, gamma: int, ngram: int):
    """Prompt-lookup draft: find the most recent earlier occurrence of
    the trailing `ngram` tokens and propose what followed it. Pads with
    zeros on no match / short continuation (padding simply gets
    rejected by the verify step — no special casing)."""
    draft = []
    if len(history) > ngram:
        tail = history[-ngram:]
        # scan right-to-left for the most recent match
        for i in range(len(history) - ngram - 1, -1, -1):
            if history[i:i + ngram] == tail:
                draft = history[i + ngram:i + ngram + gamma]
                break
    return draft + [0] * (gamma - len(draft))


class InferenceEngine:
    """Single-program inference over a (possibly sharded) param pytree.

    Sharded use: pass `shardings` pytrees for params/cache (from the
    partitioner); jit then compiles one SPMD program over the active mesh.
    """

    def __init__(self, model: Model, params, runtime: Optional[RuntimeConfig] = None,
                 mesh=None, num_microbatches: Optional[int] = None,
                 use_flash_prefill: Optional[bool] = None,
                 virtual_stages: int = 1):
        self.model = model
        self.cfg = model.cfg
        self.runtime = runtime or RuntimeConfig()
        # (B, max_seq) -> reusable KV buffers from the previous call;
        # bounded (FIFO) so varying shapes can't pin unbounded HBM
        from collections import OrderedDict
        self._cache_pool: "OrderedDict" = OrderedDict()
        self._cache_pool_cap = 2
        # Inference reads every weight every step: keep params in the
        # compute dtype so the decode loop streams half the HBM bytes
        # (the in-scan cast then no-ops and XLA elides it).
        self.params = cast_params(params, self.cfg)
        self.mesh = mesh
        S = mesh.shape.get("stage", 1) if mesh is not None else 1
        if virtual_stages > 1 and S > 1:
            # interleaved 1F1B-style schedule: permute the layer stack
            # once so each stage's contiguous shard holds its V
            # round-robin chunks (parallel/pipeline.py). Donating jit:
            # no transient second copy of the stack in HBM.
            from butterfly_tpu.parallel.pipeline import interleave_layers
            perm = jax.jit(
                partial(interleave_layers, num_layers=self.cfg.num_layers,
                        S=S, V=virtual_stages),
                donate_argnums=(0,))
            self.params = dict(self.params)
            self.params["layers"] = perm(self.params["layers"])
        elif S <= 1:
            virtual_stages = 1  # no stage axis: schedule knob is moot
        if use_flash_prefill is None:
            # on everywhere but the CPU backend (ops/__init__.py); under
            # a mesh the call sites go through ops/*_sharded (shard_map
            # over data/tensor), so a mesh does not disable them
            use_flash_prefill = kernels_default()
        self.kernel_mode = kernel_mode(use_flash_prefill)
        # kernel call sites traced by this engine's programs
        # (ops.record_kernels); `generate` prints it
        self.kernel_calls: dict = {}

        # One forward callable per step kind: the plain single-program
        # forward, or the GPipe pipeline when the mesh has stage > 1.
        # Prefill steps are always fresh (new cache, positions 0..T-1), so
        # they may use the Pallas flash kernel (cfg.attn_impl contract).
        def make_fwd(cfg, fresh=False):
            # last_index: last-token-only LM head (forward docs). The
            # GPipe forward computes full logits per microbatch — it
            # ignores the hint and the caller gathers afterwards.
            if mesh is not None and mesh.shape.get("stage", 1) > 1:
                from butterfly_tpu.parallel.pipeline import pipeline_forward
                return lambda p, t, c, pos=None, last_index=None: \
                    pipeline_forward(
                        p, cfg, t, c, mesh, num_microbatches, pos,
                        fresh=fresh, virtual_stages=virtual_stages)
            return lambda p, t, c, pos=None, last_index=None: forward(
                p, cfg, t, c, pos, fresh=fresh, last_index=last_index)

        fwd = make_fwd(self.cfg)
        prefill_cfg = self.cfg.replace(attn_impl="flash") \
            if use_flash_prefill else self.cfg
        self._fwd = fwd
        self._prefill = jax.jit(
            partial(_prefill_step, make_fwd(prefill_cfg, fresh=True)),
            donate_argnums=(2,),
        )
        self._decode = jax.jit(
            partial(_decode_step, fwd),
            static_argnums=(4,),
            donate_argnums=(2,),
        )
        # Fused generate: the write-combined window variant decodes
        # decode_window tokens per outer scan step and flushes them into
        # the cache in one ragged write (models/common.py window docs);
        # the per-step variant remains for pipeline meshes (the GPipe
        # forward manages its own cache writes) and decode_window=1.
        window = self.runtime.decode_window
        if window == 0:  # auto (config.py rationale)
            window = 16 if self.runtime.kv_quant == "int8" else 1
        self._decode_window = max(1, window) if S <= 1 else 1
        if self._decode_window > 1:
            self._generate_fused = jax.jit(
                partial(_generate_fused_win, self.cfg, self._decode_window),
                static_argnums=(4, 5, 6),
                donate_argnums=(2,),
            )
        else:
            self._generate_fused = jax.jit(
                partial(_generate_fused, fwd),
                static_argnums=(4, 5),
                donate_argnums=(2,),
            )

    # -- public API ---------------------------------------------------------

    def new_cache(self, batch: int, max_seq: Optional[int] = None) -> KVCache:
        return init_cache(self.cfg, batch, max_seq or self.runtime.max_seq_len,
                          quant=self.runtime.kv_quant)

    def prefill(self, tokens: jax.Array, true_lens: jax.Array,
                cache: KVCache) -> Tuple[jax.Array, KVCache]:
        """tokens [B,Tpad] right-padded; returns (last-token logits [B,V], cache)."""
        return self._prefill(self.params, tokens, cache, true_lens)

    def decode(self, token: jax.Array, cache: KVCache, key: jax.Array,
               sp: SamplingParams) -> Tuple[jax.Array, KVCache, jax.Array]:
        return self._decode(self.params, token, cache, key, sp)

    def generate(self, prompts: Sequence[Sequence[int]],
                 sp: Optional[SamplingParams] = None,
                 seed: int = 0, fused: bool = True) -> GenerateResult:
        """End-to-end batched generation from python-list prompts."""
        sp = sp or SamplingParams()
        n_real = len(prompts)
        # The mesh's data axis shards the batch dim: pad the request count
        # to a multiple of it (dummy rows are stripped from the result).
        if self.mesh is not None:
            dp = self.mesh.shape.get("data", 1)
            if n_real % dp != 0:
                prompts = list(prompts) + [list(prompts[0])] * (
                    dp - n_real % dp)
        tokens, true_lens = pad_prompts(prompts)
        B = tokens.shape[0]
        total = tokens.shape[1] + sp.max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt ({tokens.shape[1]}) + max_new_tokens "
                f"({sp.max_new_tokens}) = {total} exceeds the model's "
                f"max_seq_len ({self.cfg.max_seq_len})")
        # Exact KV sizing: prefill writes T slots and the decode loop
        # writes at most max(max_new, ceil(steps/C)*C) more (the windowed
        # scan rounds the step count up to a multiple of the window; its
        # tail steps write frozen tokens past `total`). Attention reads
        # the WHOLE buffer every step, so slack rows are pure HBM
        # traffic: `total + C - 1` cost 6% of the decode-loop bytes at
        # the bench shape (S 271 vs 256).
        steps = sp.max_new_tokens - 1
        iters = -(-steps // self._decode_window) if steps else 0
        max_seq = max(self.runtime.max_seq_len,
                      tokens.shape[1] + max(sp.max_new_tokens,
                                            iters * self._decode_window))
        # Reuse the previous call's (donated-through) cache buffers when
        # the shape matches: a fresh pool pays allocation + memset of
        # ~GBs per call, and stale K/V is harmless — prefill overwrites
        # positions 0..T-1 and the causal mask never reaches past each
        # row's written length.
        cache = self._cache_pool.pop((B, max_seq), None)
        if cache is None:
            cache = self.new_cache(B, max_seq)
            if self.mesh is not None:
                from butterfly_tpu.parallel.partition import shard_cache
                cache = shard_cache(cache, self.cfg, self.mesh)
        key, first_key, loop_key = jax.random.split(jax.random.PRNGKey(seed), 3)

        with self._mesh_ctx():
            logits, cache = self.prefill(jnp.asarray(tokens),
                                         jnp.asarray(true_lens), cache)
            first = sample(logits, first_key, sp)

            if fused:
                if self._decode_window > 1:
                    # static flag: every row flushes at the same offset
                    # (equal prompt lengths) -> one aliasable
                    # scalar-offset cache write per flush group
                    uniform = bool(np.all(true_lens == true_lens[0]))
                    out, lens, cache = self._generate_fused(
                        self.params, first, cache, loop_key, sp,
                        sp.max_new_tokens, uniform)
                else:
                    out, lens, cache = self._generate_fused(
                        self.params, first, cache, loop_key, sp,
                        sp.max_new_tokens)
                out, lens = np.asarray(out), np.asarray(lens)
            else:
                toks = [np.asarray(first)]
                cur = first
                key = loop_key
                for _ in range(sp.max_new_tokens - 1):
                    key, sub = jax.random.split(key)
                    cur, cache, _ = self.decode(cur, cache, sub, sp)
                    toks.append(np.asarray(cur))
                out = np.stack(toks, axis=1)
                lens = _stop_lengths(out, sp.stop_token)
                out = _mask_after_stop(out, lens, sp.stop_token)
        self._cache_pool[(B, max_seq)] = cache
        while len(self._cache_pool) > self._cache_pool_cap:
            self._cache_pool.popitem(last=False)  # FIFO-evict (frees HBM)
        return GenerateResult(tokens=out[:n_real], lengths=lens[:n_real],
                              prompt_lengths=np.asarray(true_lens)[:n_real])

    def generate_long(self, prompt: Sequence[int],
                      sp: Optional[SamplingParams] = None,
                      seed: int = 0, impl: str = "ring") -> GenerateResult:
        """Long-context generation over the mesh's `seq` axis (SURVEY §3
        call stack 5): sequence-parallel prefill (parallel/sequence.py
        sp_forward — ring attention or Ulysses) leaves the prompt's KV
        sharded over `seq` where it was computed; decode steps
        (sp_decode_step) merge per-device partial attention with
        [B,Nq,H]-sized collectives, so the long prefix is never
        regathered. Single sequence (the long-context shape); the prompt
        is right-padded to a multiple of the seq axis and the pad K/V is
        masked out of every decode step (prefill needs no mask: pads sit
        at positions causality already excludes).

        runtime.kv_quant="int8" rides straight through (ISSUE 20): the
        sharded prefix and the replicated suffix both hold codes+scales
        and every attention read dequantizes in-kernel, so the long
        prefix costs a quarter of the bf16 HBM.

        CLI surface: `butterfly generate --seq-parallel N`.
        """
        sp = sp or SamplingParams()
        if self.mesh is None or self.mesh.shape.get("seq", 1) <= 1:
            raise ValueError(
                "generate_long needs a mesh with a seq axis > 1 "
                "(CLI: --seq-parallel N)")
        if self.mesh.shape.get("stage", 1) > 1:
            raise NotImplementedError(
                "seq-parallel generation does not compose with pipeline "
                "stages (stage > 1): sp_forward runs the whole layer "
                "stack on every seq shard")
        from butterfly_tpu.models.common import init_cache
        from butterfly_tpu.parallel.sequence import (sp_decode_step,
                                                     sp_forward)

        N = self.mesh.shape["seq"]
        ids = list(prompt)
        true_len = len(ids)
        total = true_len + sp.max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt ({true_len}) + max_new_tokens "
                f"({sp.max_new_tokens}) = {total} exceeds the model's "
                f"max_seq_len ({self.cfg.max_seq_len})")
        pad = -(-true_len // N) * N
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :true_len] = np.asarray(ids, np.int32)
        plen = jnp.asarray([true_len], jnp.int32)

        key, first_key, loop_key = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        mesh = self.mesh
        kvq = self.runtime.kv_quant
        # jit wrappers cached per engine (keyed by impl + kv_quant):
        # rebuilding them per call would re-trace and recompile both
        # programs each time
        if not hasattr(self, "_sp_programs"):
            self._sp_programs = {}
        if (impl, kvq) not in self._sp_programs:
            self._sp_programs[(impl, kvq)] = (
                jax.jit(lambda p, t: sp_forward(p, self.cfg, t, mesh,
                                                impl=impl, kv_quant=kvq)),
                jax.jit(lambda p, t, pos, pre, suf, pl: sp_decode_step(
                    p, self.cfg, t, pos, pre, suf, mesh, prefix_len=pl)))
        prefill, step = self._sp_programs[(impl, kvq)]
        with self._mesh_ctx():
            logits, prefix = prefill(self.params, jnp.asarray(tokens))
            cur = sample(logits[:, true_len - 1, :], first_key, sp)
            # replicated suffix cache sized for the whole decode run
            # (quantized alongside the prefix so both segments read the
            # same representation the dense int8 path reads back)
            suffix = init_cache(self.cfg, 1, sp.max_new_tokens, quant=kvq)
            # Dispatch-ahead decode: keep up to runtime.inflight_blocks
            # sp_decode_step dispatches chained on the DEVICE token
            # before reading any back — the per-token int(np.asarray)
            # round trip otherwise serializes host and device every
            # step (the serving scheduler's _inflight pattern, single-
            # sequence edition). Positions depend only on the dispatch
            # count, never on token values, so dispatching runs ahead
            # of the host's stop-token check; tokens dispatched past a
            # stop are discarded at drain, and the dispatch count is
            # bounded by max_new_tokens - 1 so the suffix cache cannot
            # overflow.
            depth = max(1, self.runtime.inflight_blocks)
            pending = deque([cur])
            out: List[int] = []
            n_disp = 0  # decode steps dispatched so far
            key = loop_key
            while pending:
                while len(pending) <= depth and \
                        n_disp < sp.max_new_tokens - 1:
                    positions = jnp.asarray([[true_len + n_disp]],
                                            jnp.int32)
                    logits, suffix = step(self.params, cur[:, None],
                                          positions, prefix, suffix, plen)
                    key, sub = jax.random.split(key)
                    cur = sample(logits, sub, sp)
                    pending.append(cur)
                    n_disp += 1
                tok = int(np.asarray(pending.popleft())[0])
                out.append(tok)
                if sp.stop_token >= 0 and tok == sp.stop_token:
                    break  # in-flight steps past the stop are discarded

        toks = np.asarray(out, np.int32)[None]
        lens = _stop_lengths(toks, sp.stop_token)
        return GenerateResult(tokens=_mask_after_stop(toks, lens,
                                                      sp.stop_token),
                              lengths=lens,
                              prompt_lengths=np.asarray([true_len]))

    def generate_speculative(self, prompt: Sequence[int],
                             sp: Optional[SamplingParams] = None,
                             gamma: int = 4, ngram: int = 2,
                             seed: int = 0) -> "SpeculativeResult":
        """Generation with prompt-lookup speculative decoding.

        Drafts `gamma` tokens per step by matching the last `ngram`
        generated tokens against the sequence so far (the model-free
        "prompt lookup" scheme) and verifies the whole draft in ONE
        (gamma+1)-token warm forward. Accepted drafts advance the
        sequence several tokens per forward. At temperature 0 the
        output is token-for-token IDENTICAL to plain greedy decode
        (`_accept_drafts` fast path); at temperature > 0 each draft is
        accepted with probability p(draft) and the first rejection
        resamples from the residual (sampling.speculative_accept — the
        Leviathan et al. rejection-sampling correction, exact for the
        one-hot prompt-lookup proposal), so the output DISTRIBUTION
        equals plain sampling. Either way speculation only changes how
        many forwards the tokens take.

        Correctness of the KV cache under rejection: a verify step
        writes K/V for every draft position; rejected positions hold
        stale K/V, but the next verify starts at the first rejected
        position and rewrites all of them before any query can attend
        that far (write-then-attend in attention_block), so stale
        entries are never visible.

        Single-sequence, host-looped (per-row accept counts diverge;
        the BATCHED multi-slot edition lives in the serving engine's
        spec block — engine/serving.py _spec_scan).
        """
        sp = sp or SamplingParams()
        if gamma < 1 or ngram < 1:
            raise ValueError("gamma and ngram must be >= 1")
        if self.mesh is not None and (self.mesh.shape.get("data", 1) > 1
                                      or self.mesh.shape.get("stage", 1) > 1):
            # one sequence can't be data-sharded, and the GPipe forward
            # has no single-microbatch warm-verify path
            raise NotImplementedError(
                "speculative decoding supports tensor/expert meshes only")

        tokens, true_lens = pad_prompts([list(prompt)])
        total = tokens.shape[1] + sp.max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds max_seq_len")
        # + gamma slack: the last verify may write past `total`
        cache = self.new_cache(1, max(self.runtime.max_seq_len,
                                      total + gamma))
        if self.mesh is not None:
            from butterfly_tpu.parallel.partition import shard_cache
            cache = shard_cache(cache, self.cfg, self.mesh)

        stochastic = not sp.is_greedy
        key, first_key = jax.random.split(jax.random.PRNGKey(seed))
        with self._mesh_ctx():
            logits, cache = self.prefill(jnp.asarray(tokens),
                                         jnp.asarray(true_lens), cache)
            cur = int(np.asarray(sample(logits, first_key, sp))[0]) \
                if stochastic else int(jnp.argmax(logits[0]))
        history = list(prompt) + [cur]
        out = [cur]
        forwards = 1  # the prefill produced the first token
        accepted_total = 0

        # greedy keeps its argmax-on-device program (+_accept_drafts
        # fast path, byte-identical to plain greedy decode); sampling
        # fetches the verify logits and runs the rejection-sampling
        # correction (the shared speculative_accept kernel)
        verify = self._verify_program(gamma, logits=stochastic)
        temps = jnp.asarray([sp.temperature], jnp.float32)
        while len(out) < sp.max_new_tokens and \
                not (sp.stop_token >= 0 and out[-1] == sp.stop_token):
            draft = _ngram_draft(history, gamma, ngram)
            pos0 = len(history) - 1  # cur's absolute position
            toks = jnp.asarray([[cur] + draft], jnp.int32)
            positions = pos0 + jnp.arange(gamma + 1)[None, :]
            with self._mesh_ctx():
                ver, cache = verify(self.params, toks, cache, positions)
            forwards += 1

            if stochastic:
                from butterfly_tpu.engine.sampling import speculative_accept
                key, sub = jax.random.split(key)
                em, n_acc = speculative_accept(
                    ver, jnp.asarray([draft], jnp.int32), sub, temps,
                    sp.top_k, sp.top_p)
                n = int(np.asarray(n_acc)[0]) + 1
                emitted = np.asarray(em)[0, :n].tolist()
            else:
                emitted = _accept_drafts(draft, np.asarray(ver[0]))
            accepted_total += len(emitted) - 1
            # valid cache entries: cur + the accepted drafts
            new_len = pos0 + len(emitted)
            cache = cache._replace(
                length=jnp.asarray([new_len], jnp.int32))
            for t in emitted:
                out.append(t)
                history.append(t)
                if len(out) >= sp.max_new_tokens or \
                        (sp.stop_token >= 0 and t == sp.stop_token):
                    break
            cur = out[-1]

        if sp.stop_token >= 0 and sp.stop_token in out:
            out = out[:out.index(sp.stop_token) + 1]
        return SpeculativeResult(
            tokens=np.asarray(out, np.int32), forwards=forwards,
            accepted_drafts=accepted_total)

    def _verify_program(self, gamma: int, logits: bool = False):
        """jitted (gamma+1)-token warm verify. Returns per-position
        greedy next tokens [B, gamma+1] (logits=False — the greedy
        fast path keeps argmax on device) or the raw per-position
        logits [B, gamma+1, V] (logits=True — the stochastic path
        feeds them to the rejection-sampling correction). Cached per
        (gamma, flavor)."""
        if not hasattr(self, "_verify_cache"):
            self._verify_cache = {}
        cache_key = (gamma, logits)
        if cache_key not in self._verify_cache:
            fwd = self._fwd

            def step(params, toks, cache, positions, _logits=logits):
                out, cache = fwd(params, toks, cache, positions)
                if not _logits:
                    out = jnp.argmax(out, axis=-1).astype(jnp.int32)
                return out, cache

            self._verify_cache[cache_key] = jax.jit(step, donate_argnums=(2,))
        return self._verify_cache[cache_key]

    @contextlib.contextmanager
    def _mesh_ctx(self):
        with mesh_ctx(self.mesh), record_kernels(self.kernel_calls):
            yield


# ---------------------------------------------------------------------------
# jitted step functions (module-level so jit caches persist across engines)
# ---------------------------------------------------------------------------

def _prefill_step(fwd, params, tokens, cache, true_lens):
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    # last real token's logits only (forward last_index docs); paths
    # that don't honor the hint return full-T logits — gather those.
    logits, cache = fwd(params, tokens, cache, positions,
                        last_index=true_lens - 1)
    if logits.shape[1] != 1:
        logits = jnp.take_along_axis(logits, (true_lens - 1)[:, None, None],
                                     axis=1)
    cache = cache._replace(length=true_lens.astype(jnp.int32))
    return logits[:, 0, :], cache


def _decode_step(fwd, params, token, cache, key, sp: SamplingParams):
    logits, cache = fwd(params, token[:, None], cache)
    key, sub = jax.random.split(key)
    nxt = sample(logits[:, -1, :], sub, sp)
    return nxt, cache, key


def _generate_fused(fwd, params, first, cache, key,
                    sp: SamplingParams, max_new: int):
    """lax.scan over decode steps — the whole generation is one XLA program.

    Sequences that hit the stop token keep stepping (static shapes) but
    their outputs are frozen via the done mask; no recompilation, no host
    sync until the final device->host copy.
    """
    def body(carry, _):
        cur, cache, key, done = carry
        logits, cache = fwd(params, cur[:, None], cache)
        key, sub = jax.random.split(key)
        nxt = sample(logits[:, -1, :], sub, sp)
        nxt = jnp.where(done, cur, nxt)
        if sp.stop_token >= 0:
            done = done | (nxt == sp.stop_token)
        return (nxt, cache, key, done), nxt

    done0 = (first == sp.stop_token) if sp.stop_token >= 0 \
        else jnp.zeros_like(first, dtype=bool)
    (_, cache, _, _), toks = jax.lax.scan(
        body, (first, cache, key, done0), None, length=max_new - 1)
    out = jnp.concatenate([first[:, None], toks.T], axis=1)  # [B, max_new]
    lens = _stop_lengths_jnp(out, sp.stop_token)
    # The final cache is returned so the donated input cache has an
    # output to alias (otherwise XLA keeps a second full pool live for
    # the whole scan) AND so generate() can recycle the buffers for the
    # next call instead of allocating fresh pools.
    return out, lens, cache


def _generate_fused_win(cfg: ModelConfig, C: int, params, first, cache, key,
                        sp: SamplingParams, max_new: int,
                        uniform: bool = False):
    """Write-combined fused generate: C decode steps per outer scan
    iteration against (cache + prior window steps + self), then ONE
    ragged cache write for all C tokens (flush_window). Token-for-token
    identical to _generate_fused — the window steps store the cache's
    exact representation (int8 codes + scales in quant mode) and keys
    split in the same order — while amortizing the dominant whole-pool
    copy the per-step cache update costs on TPU (models/common.py
    window docs). The C steps are unrolled, so the window is a plain
    Python list of per-step K/V values — no device buffer, no carry.
    """
    from butterfly_tpu.models.common import decode_step_win, flush_window

    B = first.shape[0]
    steps = max_new - 1
    iters = -(-steps // C) if steps else 0

    def body(carry, _):
        cur, cache, key, done = carry
        toks, window = [], []
        for j in range(C):
            key, sub = jax.random.split(key)
            logits, new_kv = decode_step_win(
                params, cfg, cur[:, None], cache, window, j)
            window.append(new_kv)
            nxt = sample(logits[:, -1, :], sub, sp)
            nxt = jnp.where(done, cur, nxt)
            if sp.stop_token >= 0:
                done = done | (nxt == sp.stop_token)
            cur = nxt
            toks.append(nxt)
        cache = flush_window(cache, window, uniform=uniform)
        return (cur, cache, key, done), jnp.stack(toks)

    done0 = (first == sp.stop_token) if sp.stop_token >= 0 \
        else jnp.zeros_like(first, dtype=bool)
    carry0 = (first, cache, key, done0)
    (_, cache, *_), toks = jax.lax.scan(body, carry0, None, length=iters)
    toks = toks.reshape(iters * C, B)[:steps] if steps \
        else jnp.zeros((0, B), first.dtype)
    out = jnp.concatenate([first[:, None], toks.T], axis=1)  # [B, max_new]
    lens = _stop_lengths_jnp(out, sp.stop_token)
    return out, lens, cache


def _stop_lengths_jnp(out: jax.Array, stop: int) -> jax.Array:
    B, T = out.shape
    if stop < 0:
        return jnp.full((B,), T, jnp.int32)
    hit = out == stop
    any_hit = hit.any(axis=1)
    first_hit = jnp.argmax(hit, axis=1)
    return jnp.where(any_hit, first_hit + 1, T).astype(jnp.int32)


def _stop_lengths(out: np.ndarray, stop: int) -> np.ndarray:
    return np.asarray(_stop_lengths_jnp(jnp.asarray(out), stop))


def _mask_after_stop(out: np.ndarray, lens: np.ndarray, stop: int) -> np.ndarray:
    if stop < 0:
        return out
    mask = np.arange(out.shape[1])[None, :] >= lens[:, None]
    out = out.copy()
    out[mask] = stop
    return out


def cast_params(params, cfg: ModelConfig):
    """One-time cast of the weight pytree to the compute dtype.

    Device-resident cast (jit, donating the source) so a 70B f32 tree
    never round-trips the host; sharded inputs keep their shardings.
    """
    target = jnp.dtype(cfg.dtype)
    leaves = jax.tree.leaves(params)
    if all(a.dtype == target or not jnp.issubdtype(a.dtype, jnp.floating)
           for a in leaves):
        return params

    @partial(jax.jit, donate_argnums=(0,))
    def cast(p):
        return jax.tree.map(
            lambda a: a.astype(target)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)

    return cast(params)


def pad_prompts(prompts: Sequence[Sequence[int]], pad_id: int = 0
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Right-pad variable-length prompts to a rectangle."""
    lens = np.asarray([len(p) for p in prompts], np.int32)
    T = int(lens.max())
    out = np.full((len(prompts), T), pad_id, np.int32)
    for i, p in enumerate(prompts):
        out[i, :len(p)] = np.asarray(p, np.int32)
    return out, lens
