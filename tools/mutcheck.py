#!/usr/bin/env python
"""Mutation-testing smoke: prove the suite KILLS planted bugs.

The reference intended mutation testing (cargo-mutants artifacts in its
.gitignore — SURVEY.md §4); this is the framework's analogue, sized for
CI: a curated set of single-line mutations in numerically-load-bearing
code, each of which MUST make its covering test subset fail. A mutant
that survives means the tests have a blind spot — the tool exits 1 and
names it.

Usage:  python tools/mutcheck.py            # run all mutants
        python tools/mutcheck.py --list     # show the catalogue

Each mutation is applied in-place, the covering tests are run in a
subprocess, and the file is restored from git (requires a clean tree
for the mutated files).
"""
from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: (file, original, mutated, covering-tests, extra-env) — original must
#: occur exactly once in the file so the mutation is unambiguous.
MUTANTS = [
    # rms_norm: drop the rsqrt normalization direction
    ("butterfly_tpu/models/common.py",
     "x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)",
     "x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1.0)",
     ["tests/test_models.py"], {}),
    # causal mask off-by-one: attend to the future
    ("butterfly_tpu/models/common.py",
     "return j <= positions[:, :, None]",
     "return j <= positions[:, :, None] + 1",
     ["tests/test_models.py"], {}),
    # decode fast path: self-term dropped from the merged softmax.
    # Killed by the prefill-whole vs incremental-decode invariant
    # (test_models) — NOT by test_engine, whose compared paths share
    # decode_attend (first mutcheck run found that blind spot).
    ("butterfly_tpu/models/common.py",
     "out = out + p[..., -1:].astype(v_new.dtype) * v_new.reshape(B, Kv, 1, H)",
     "out = out + 0 * p[..., -1:].astype(v_new.dtype) * v_new.reshape(B, Kv, 1, H)",
     ["tests/test_models.py"], {}),
    # int8 KV quantizer: wrong scale denominator (codes clip hard)
    ("butterfly_tpu/models/common.py",
     "scale = jnp.where(amax > 0, amax / 127.0, 1.0)",
     "scale = jnp.where(amax > 0, amax / 64.0, 1.0)",
     ["tests/test_kv_quant.py"], {}),
    # decode window: window K-scales dropped from the merged softmax
    # (quantized window scores would be raw code dots)
    ("butterfly_tpu/models/common.py",
     "s_w = s_w * jnp.moveaxis(wk_s, 0, -1)[:, :, None, :]",
     "s_w = s_w * 1.0",
     ["tests/test_kv_quant.py"], {}),
    # decode window flush (uniform fast path): off-by-one write offset —
    # the flush group lands one slot late, orphaning slot `start`
    ("butterfly_tpu/models/common.py",
     "new_k = lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, s0, 0))",
     "new_k = lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, s0 + 1, 0))",
     ["tests/test_kv_quant.py"], {}),
    # prefix cache: chain digest forgets the parent (a page would match
    # regardless of what precedes it)
    ("butterfly_tpu/cache/prefix.py",
     "m = hashlib.sha256(h)",
     "m = hashlib.sha256()",
     ["tests/test_prefix.py"], {}),
    # prefix cache: refcount never increments (shared pages freed while
    # still attached)
    ("butterfly_tpu/cache/prefix.py",
     "self._ref[pid] += 1",
     "self._ref[pid] += 0",
     ["tests/test_prefix.py"], {}),
    # prefix cache: register the last sampled (never-written) token's
    # page as reusable content
    ("butterfly_tpu/sched/scheduler.py",
     "return len(req.all_tokens) - 1",
     "return len(req.all_tokens)",
     ["tests/test_prefix.py"], {}),
    # stop sequences: leak the first byte of the stop text
    ("butterfly_tpu/serve/server.py",
     "out = self.text[self.released:cut]",
     "out = self.text[self.released:cut + 1]",
     ["tests/test_server.py"], {}),
    # speculative decoding: accept mismatched drafts in the engine's
    # host accept loop (generate_speculative greedy fast path)
    ("butterfly_tpu/engine/engine.py",
     "if d != int(greedy[i]):",
     "if False and d != int(greedy[i]):",
     ["tests/test_speculative.py"], {}),
    # speculative serving: accept mismatched drafts in the DEVICE
    # accept kernel's greedy rows (the serving spec block's byte-parity
    # contract — test_sched greedy parity + the kernel unit tests)
    ("butterfly_tpu/engine/sampling.py",
     "drafts == greedy_tok[:, :gamma]",
     "jnp.ones_like(drafts, dtype=bool)",
     ["tests/test_sched.py", "tests/test_spec_sampling.py"], {}),
    # allocator: hand out one page fewer than needed. Must pin the
    # PYTHON backend: with the native lib built, the scheduler uses the
    # C++ twin and a Python-side mutation is invisible (first mutcheck
    # run found that blind spot too).
    ("butterfly_tpu/cache/allocator.py",
     "want = -(-new_length // self.page_size)",
     "want = new_length // self.page_size",
     ["tests/test_sched.py"], {"BUTTERFLY_NATIVE": "0"}),
    # scheduler: chunked prefill skips the final prompt token
    ("butterfly_tpu/sched/scheduler.py",
     "chunk = prefix[req.prefilled:end]",
     "chunk = prefix[req.prefilled:max(req.prefilled + 1, end - 1)]",
     ["tests/test_sched.py"], {}),
    # paged write: scatter every token to page offset 0
    ("butterfly_tpu/cache/paged.py",
     "offset = pos % page",
     "offset = pos * 0",
     ["tests/test_paged.py"], {}),
    # paged decode kernel: attend one not-yet-written slot past each
    # sequence's length
    ("butterfly_tpu/ops/paged_attention.py",
     "mask = group_ok & (pos < length)",
     "mask = group_ok & (pos <= length)",
     ["tests/test_kernels.py"], {}),
    # paged decode kernel: K scales dropped (int8 scores = raw code dots)
    ("butterfly_tpu/ops/paged_attention.py",
     "s = s * ks_ref[0]",
     "s = s * 1.0",
     ["tests/test_kernels.py"], {}),
    # paged decode kernel: every layer attends layer 0's pages of the
    # whole pool it is handed
    ("butterfly_tpu/ops/paged_attention.py",
     "return (ly[0], t[s, jnp.minimum(j, max_pages - 1)], 0, 0, 0)",
     "return (ly[0] * 0, t[s, jnp.minimum(j, max_pages - 1)], 0, 0, 0)",
     ["tests/test_kernels.py"], {}),
    # contiguous int8 attend: V scale not folded into the probs
    ("butterfly_tpu/models/common.py",
     "probs = probs * v_scale[:, :, None, None, :]",
     "probs = probs * 1.0",
     ["tests/test_kv_quant.py"], {}),
    # ring attention: one rotation short (each device misses one
    # neighbor's K/V block)
    ("butterfly_tpu/parallel/sequence.py",
     "step, (stats, k, v, k_pos, k_scale, v_scale), None, length=N)",
     "step, (stats, k, v, k_pos, k_scale, v_scale), None, length=N - 1)",
     ["tests/test_sequence.py"], {}),
    # flash-stats merge (ISSUE 20): drop the running-max correction on
    # the a-leg — partials whose local max is below the joint max keep
    # their unrescaled weight, so every ring rotation / SP chunk merge
    # over-counts the smaller-max side. Killed by the four-shard merge
    # algebra test in tests/test_longctx.py (and the ring parity grid).
    ("butterfly_tpu/ops/ring_attention.py",
     "c_a = jnp.exp(m_a - m)",
     "c_a = jnp.exp(m_a - m_a)",
     ["tests/test_longctx.py"], {}),
    # sp_decode partial-softmax merge: global max skipped (per-device
    # exp shifts disagree, denominators mis-merge)
    ("butterfly_tpu/parallel/sequence.py",
     'm_g = lax.pmax(m_i, "seq")',
     "m_g = m_i",
     ["tests/test_sequence.py"], {}),
    # EP a2a dispatch: counting-sort slot ignores the running count
    # (every assignment of an expert lands in slot 0)
    ("butterfly_tpu/parallel/expert.py",
     "pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(A), g_flat]",
     "pos = 0 * (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(A), g_flat]",
     ["tests/test_expert.py"], {}),
    # speculative serving scan: length rollback off by one (the first
    # rejected position's stale K/V becomes attendable). The anchor
    # used to live in the scheduler's host accept loop; it moved into
    # the on-device scan when acceptance did.
    ("butterfly_tpu/engine/serving.py",
     "cache = cache._replace(lengths=jnp.where(live, W + m, W))",
     "cache = cache._replace(lengths=jnp.where(live, W + m + 1, W))",
     ["tests/test_sched.py"], {}),
    # tree speculation (ISSUE 19): collapse the tree-attention
    # ancestor mask to all-ones — every node attends EVERY chunk
    # position in range, so sibling branches leak into each other's
    # scores (a depth-2 node sees its parent's rejected sibling). The
    # realized greedy path's logits shift and the tree parity grid
    # (test_sched k x inflight x window, byte-identical vs spec-off)
    # diverges within a few tokens.
    ("butterfly_tpu/engine/serving.py",
     "& jnp.transpose(tree_bits, (1, 0, 2))",
     "& True",
     ["tests/test_sched.py"], {}),
    # write-combined KV window (ISSUE 12): drop the flush's K-pool
    # scatter — staged K bytes never land, so after a drain the pool
    # serves zeros for flushed positions. Killed by the int8
    # quantize-on-flush parity test (token parity AND a byte-level
    # pool compare vs the per-token path — the float smoke model's
    # greedy argmax can shrug off zeroed K, the int8 path cannot).
    ("butterfly_tpu/cache/paged.py",
     "k_pages = cache.k_pages.at[:, flat_pages, :, flat_off].set(kv_vals)",
     "k_pages = cache.k_pages",
     ["tests/test_kv_quant.py", "tests/test_sched.py"], {}),
    # write-combined KV window, spec: flush without rollback truncation
    # — win_len advances by the full gamma+1 verify width instead of
    # the ACCEPTED count, so rejected drafts become attendable/flushable
    # and the window desynchronizes from the token history (killed by
    # the spec parity grid + the rejection-never-flushed pool probe)
    ("butterfly_tpu/engine/serving.py",
     "wlen = jnp.where(live, wlen + m, wlen)",
     "wlen = jnp.where(live, wlen + C, wlen)",
     ["tests/test_sched.py"], {}),
    # draft-model speculation (ISSUE 14): draft KV length advances by
    # the DRAFTED count (the γ+1 micro-step writes stay live) instead
    # of the accepted count — rejected drafts' K/V become attendable,
    # the draft desynchronizes from the history (wrong positions, wrong
    # context), and the draft_len == hist_len - 1 invariant breaks.
    # Killed by the draft spec parity-grid file's rollback-exactness
    # probe (tests/test_draft.py pins the invariant mid-flight on a
    # rejection-heavy prompt).
    ("butterfly_tpu/engine/serving.py",
     "return dstate._replace(length=jnp.where(live, dlen0 + m, dlen0))",
     "return dstate",
     ["tests/test_draft.py"], {}),
    # warm-prefix flash prefill (ISSUE 13): drop the prefix-length mask
    # — every row would attend the FULL cached-prefix block run,
    # including recycled-buffer garbage past its start, zero padding,
    # and (in serving) the chunk's own in-cache copy. Killed by the
    # kernel unit's garbage-past-start bit-compare and the dense-insert
    # parity checks in tests/test_warm_prefill.py.
    ("butterfly_tpu/ops/flash_attention.py",
     "mask = cols < start",
     "mask = cols >= 0",
     ["tests/test_warm_prefill.py"], {}),
    # flight recorder (ISSUE 15): weaken the SLO-burn trigger predicate
    # to threshold=inf — the anomaly post-mortem would silently never
    # fire on a burning error budget. Killed by the trigger tests in
    # tests/test_obs.py (poll at burn >= threshold must dump).
    ("butterfly_tpu/obs/ticklog.py",
     "if burn >= self.slo_burn_threshold and burn > 0.0:",
     'if burn >= float("inf") and burn > 0.0:',
     ["tests/test_obs.py"], {}),
    # alert rules (ISSUE 16): collapse the sustained-window guard so a
    # rule fires on a SINGLE above-threshold sample — every transient
    # blip would page. Killed by the alert-rule unit tests (one hot
    # sample must NOT fire; a full window must).
    ("butterfly_tpu/obs/timeseries.py",
     "if len(tail) < rule.window:",
     "if len(tail) < 1:",
     ["tests/test_timeseries.py"], {}),
    # workload generator: the Poisson arrival process ignores its rate
    # (every open-loop bench/sweep would silently offer ~1 req/s
    # regardless of the requested load) — the arrival-statistics test
    # must pin the mean inter-arrival to 1/rate
    ("butterfly_tpu/workload/arrivals.py",
     "dt = rng.expovariate(self.rate)",
     "dt = rng.expovariate(1.0)",
     ["tests/test_workload.py"], {}),
    # -- static-analyzer mutants (ISSUE 11): weaken one predicate per
    # rule; the fixture suite's EXACT positive counts must fail. The
    # checker is mutation-tested like the kernels — a rule that stops
    # firing must never pass silently.
    # BTF001: accept any keyword list as "has a timeout"
    ("tools/staticrules/http_timeout.py",
     'if any(kw.arg == "timeout" for kw in node.keywords):',
     "if node.keywords or not node.keywords:",
     ["tests/test_staticcheck.py"], {}),
    # BTF002: donating calls stop poisoning their arguments
    ("tools/staticrules/donation.py",
     "poison = poison | self._donated_handles(stmt)",
     "poison = poison | set()",
     ["tests/test_staticcheck.py"], {}),
    # BTF003: .item() dropped from the sync markers
    ("tools/staticrules/host_sync.py",
     'if name in ("item", "tolist", "block_until_ready") and \\',
     'if name in ("tolist", "block_until_ready") and \\',
     ["tests/test_staticcheck.py"], {}),
    # BTF004: every .acquire() counts as bounded
    ("tools/staticrules/locks.py",
     'if any(kw.arg == "timeout" for kw in node.keywords) or \\',
     "if (node.keywords is not None) or \\",
     ["tests/test_staticcheck.py"], {}),
    # BTF005: wall-clock reads allowed
    ("tools/staticrules/determinism.py",
     'if dotted == "time.time":',
     'if dotted == "time.time_never":',
     ["tests/test_staticcheck.py"], {}),
    # BTF006: key reuse never flagged
    ("tools/staticrules/prng.py",
     "if h in consumed or h in new:",
     "if h in consumed and h in new:",
     ["tests/test_staticcheck.py"], {}),
    # mixed dispatch (ISSUE 18): drop the prefill_inline_budget bound —
    # every waiting request would enter prefill phase at once, so one
    # fused scan step chews an unbounded number of prompt tokens while
    # every decode slot waits on that step's forward (exactly the ITL
    # tail the knob exists to cap). Killed by the inline-budget cap
    # test in tests/test_mixed_dispatch.py (concurrent prefill lanes
    # must never exceed prefill_inline_budget // chunk_width).
    ("butterfly_tpu/sched/scheduler.py",
     "self._mixed_max_pf = max(1, rt.prefill_inline_budget // self._mixed_chunk)",
     "self._mixed_max_pf = engine.num_slots",
     ["tests/test_mixed_dispatch.py"], {}),
    # elastic fleet (ISSUE 17): invert the scale-down hysteresis guard —
    # a shrink would be HELD only after the quiet window and allowed
    # inside it, so a grow->shrink->grow flap pays the warmup on every
    # cycle. Killed by the autoscaler unit grid (the hysteresis test
    # pins both branches: held inside the window, allowed after it).
    ("butterfly_tpu/fleet/autoscale.py",
     "if now - last < pol.cooldown_down_s:",
     "if now - last >= pol.cooldown_down_s:",
     ["tests/test_autoscale.py"], {}),
]


def run_tests(tests, extra_env) -> bool:
    """True if the covering tests PASS (i.e. the mutant survived)."""
    import os
    env = dict(os.environ, **extra_env)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", *tests],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=1200, env=env)
    return r.returncode == 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list:
        for f, orig, mut, tests, env in MUTANTS:
            print(f"{f}: {orig!r} -> {mut!r}  [{' '.join(tests)}] {env}")
        return 0

    dirty = subprocess.run(
        ["git", "diff", "--name-only"], cwd=REPO,
        capture_output=True, text=True).stdout.split()
    mutated_files = {m[0] for m in MUTANTS}
    if mutated_files & set(dirty):
        print(f"refusing to run: uncommitted changes in {mutated_files & set(dirty)}")
        return 2

    survived = []
    for i, (fname, orig, mut, tests, extra_env) in enumerate(MUTANTS):
        path = REPO / fname
        src = path.read_text()
        assert src.count(orig) == 1, f"ambiguous mutation site in {fname}"
        print(f"[{i + 1}/{len(MUTANTS)}] {fname}: {orig[:50]!r}...",
              flush=True)
        path.write_text(src.replace(orig, mut))
        try:
            if run_tests(tests, extra_env):
                survived.append((fname, orig))
                print("  SURVIVED — tests have a blind spot", flush=True)
            else:
                print("  killed", flush=True)
        finally:
            subprocess.run(["git", "checkout", "--", fname], cwd=REPO,
                           check=True)

    if survived:
        print(f"\n{len(survived)} mutant(s) survived:")
        for fname, orig in survived:
            print(f"  {fname}: {orig!r}")
        return 1
    print(f"\nall {len(MUTANTS)} mutants killed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
