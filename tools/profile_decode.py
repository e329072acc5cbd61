#!/usr/bin/env python
"""XProf the windowed int8 decode step at the bench operating point.

Captures a trace of ONLY the fused decode program (prefill + first sample
run outside the trace window), converts the xplane with xprof's
`hlo_stats` tool, and prints the top HLO ops by self time. A device
trace needs a device: without an accelerator this exits non-zero.

`--serving` traces the SERVING path's fused decode block instead: one
Scheduler tick's k-step jitted scan (engine._decode_scan) over the paged
pool, warmed through real admissions so the trace window holds exactly
one block dispatch.

`--prefill` traces one batched [B, Tbucket] prefill dispatch
(engine.prefill_batch): a gang of waiting requests is admitted inside
the trace window after the program compiled off the clock — the
admission-path twin of --serving.

`--pipeline` traces TWO chained in-flight decode blocks (dispatch-ahead,
ISSUE 5): block 2 is dispatched on block 1's device-resident carry
before block 1 is drained, so the trace shows whether the device runs
the blocks back-to-back (no bubble) while the host sits in between.

`--spec` traces one batched SPECULATIVE block (ISSUE 9): k rounds of
draft + [S, gamma+1] multi-slot verify + on-device accept as one
jitted scan (engine._spec_scan) — the speculative twin of --serving.
Add `--tree-width w [--tree-nodes N]` (ISSUE 19) to trace the token-
TREE variant instead (engine._spec_tree_scan: [S, N] single-dispatch
tree verify under the tree-attention mask) — the TPU tree point is
this flag flip.

Usage: python tools/profile_decode.py [--max-new N] [--out DIR]
       python tools/profile_decode.py --serving [--steps-per-tick K]
       python tools/profile_decode.py --prefill [--prefill-max-batch B]
       python tools/profile_decode.py --pipeline [--steps-per-tick K]
       python tools/profile_decode.py --spec [--gamma G]
       python tools/profile_decode.py --spec --draft-source model \
           --tree-width 2 [--tree-nodes N]
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-new", type=int, default=128)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--preset", default="1b", choices=("1b", "8b"),
                    help="'1b' (round-4 proxy) or '8b' (config of record)")
    ap.add_argument("--out", default=None, help="trace dir (default: tmp)")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--serving", action="store_true",
                    help="trace one fused SERVING decode block "
                         "(Scheduler + ServingEngine paged path) instead "
                         "of the offline engine's fused scan")
    ap.add_argument("--steps-per-tick", type=int, default=16,
                    help="fused block width for --serving (matches "
                         "RuntimeConfig.decode_steps_per_tick)")
    ap.add_argument("--prefill", action="store_true",
                    help="trace one batched [B, Tbucket] prefill "
                         "dispatch (group admission, "
                         "engine.prefill_batch) instead of a decode "
                         "program")
    ap.add_argument("--prefill-max-batch", type=int, default=8,
                    help="gang width for --prefill (matches "
                         "RuntimeConfig.prefill_max_batch; clamped to "
                         "--batch)")
    ap.add_argument("--pipeline", action="store_true",
                    help="trace TWO chained in-flight serving decode "
                         "blocks (dispatch-ahead: block 2 dispatched "
                         "on block 1's device carry before block 1 is "
                         "drained) — shows whether the device runs "
                         "them back-to-back with no bubble")
    ap.add_argument("--spec", action="store_true",
                    help="trace ONE batched speculative verify block "
                         "(engine._spec_scan: draft + multi-slot "
                         "verify + on-device accept rounds as one "
                         "jitted scan) — the speculative twin of "
                         "--serving")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft width for --spec (matches "
                         "RuntimeConfig.speculative_gamma)")
    ap.add_argument("--draft-source", default="ngram",
                    help="draft source for --spec (matches "
                         "RuntimeConfig.draft_model): 'ngram' = prompt "
                         "lookup, 'model' = the on-device draft model "
                         "(its per-round micro-steps land inside the "
                         "traced scan) — the ROADMAP item 3 TPU "
                         "speedup point is this flag flip")
    ap.add_argument("--tree-width", type=int, default=0,
                    help="token-TREE speculation for --spec (matches "
                         "RuntimeConfig.spec_tree_width, ISSUE 19): "
                         "branch top-WIDTH children per draft expansion "
                         "and verify the whole tree in one forward — "
                         "the TPU tree trace is this flag flip. "
                         "Requires --draft-source model; 0 = linear")
    ap.add_argument("--tree-nodes", type=int, default=0,
                    help="tree node budget N for --tree-width (matches "
                         "RuntimeConfig.spec_tree_nodes; 0 = auto "
                         "gamma+1, equal verify FLOPs vs the linear "
                         "chain)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="truncation depth for --draft-source model "
                         "(matches RuntimeConfig.draft_layers; 0 = "
                         "num_layers/4, floor 1)")
    ap.add_argument("--long-context", action="store_true",
                    help="trace ONE seq-parallel prefill chunk dispatch "
                         "(engine.sp_prefill_chunk: ring attention over "
                         "the mesh's seq axis, K/V scattered into the "
                         "paged pool) plus one fused decode block "
                         "beside it — the ISSUE 20 scheduler lane. "
                         "Builds a seq=4 mesh; the device count must be "
                         "a multiple of 4")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from butterfly_tpu.core.compile_cache import place_compile_cache
    from butterfly_tpu.core.config import ModelConfig, RuntimeConfig
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    from butterfly_tpu.engine.engine import pad_prompts
    from butterfly_tpu.engine.sampling import sample
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.obs.benchmark import require_chip
    from butterfly_tpu.quant.int8 import init_params_by_leaf

    require_chip("tools/profile_decode.py")
    place_compile_cache()
    if args.preset == "8b":
        from butterfly_tpu.core.config import llama3_8b
        cfg = llama3_8b().replace(max_seq_len=2048)
    else:
        cfg = ModelConfig(arch="llama", vocab_size=32000, hidden_size=2048,
                          num_layers=16, num_heads=16, num_kv_heads=8,
                          head_dim=128, intermediate_size=5632,
                          max_seq_len=2048)

    model = Model(cfg)
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    kv_quant = "int8"
    if args.long_context:
        return _profile_longctx(args, model, params, kv_quant)
    if args.prefill:
        return _profile_prefill_batch(args, model, params, kv_quant)
    if args.pipeline:
        return _profile_pipeline(args, model, params, kv_quant)
    if args.spec:
        return _profile_spec_block(args, model, params, kv_quant)
    if args.serving:
        return _profile_serving_block(args, model, params, kv_quant)
    engine = InferenceEngine(
        model, params,
        RuntimeConfig(max_seq_len=args.prompt_len + args.max_new,
                      kv_quant=kv_quant))

    rng = np.random.RandomState(0)
    prompts = rng.randint(1, cfg.vocab_size,
                          (args.batch, args.prompt_len)).tolist()
    sp = SamplingParams(max_new_tokens=args.max_new)

    # compile both programs, then replicate generate()'s body so the
    # trace window contains ONLY the fused decode scan
    engine.generate(prompts, sp)
    tokens, true_lens = pad_prompts(prompts)
    C = engine._decode_window
    steps = sp.max_new_tokens - 1
    iters = -(-steps // C) if steps else 0
    max_seq = max(engine.runtime.max_seq_len,
                  tokens.shape[1] + max(sp.max_new_tokens, iters * C))
    cache = engine._cache_pool.pop((args.batch, max_seq), None)
    if cache is None:
        cache = engine.new_cache(args.batch, max_seq)
    key, first_key, loop_key = jax.random.split(jax.random.PRNGKey(0), 3)
    logits, cache = engine.prefill(jnp.asarray(tokens),
                                   jnp.asarray(true_lens), cache)
    first = sample(logits, first_key, sp)
    jax.block_until_ready(first)

    logdir = args.out or tempfile.mkdtemp(prefix="decode_trace_")
    fused_args = (engine.params, first, cache, loop_key, sp,
                  sp.max_new_tokens)
    if C > 1:
        fused_args += (bool(np.all(true_lens == true_lens[0])),)
    jax.profiler.start_trace(logdir)
    out, lens, cache = engine._generate_fused(*fused_args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    return _report(logdir, args.top)


def _profile_serving_block(args, model, params, kv_quant: str) -> int:
    """Trace ONE fused serving decode block (ISSUE 3): a Scheduler is
    warmed through real admissions until every slot decodes, then a
    single k-step block is dispatched inside the trace window — the
    program one tick() pays for, including the on-device sampling, RNG
    fold-in, and EOS/budget masking."""
    import jax
    import numpy as np

    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    k = args.steps_per_tick
    cfg = model.cfg
    # budget for the warmup blocks PLUS the traced one (a request that
    # finishes during warmup would leave the traced dispatch a no-op);
    # prefill_chunk sized to admit the whole batch in one tick: the
    # warmup then costs ~3 ticks, so slots can't finish (and free)
    # before the trace window captures a FULL-batch block
    max_new = max(args.max_new, 3 * k + 8)
    rt = RuntimeConfig(max_batch_size=args.batch,
                       max_seq_len=args.prompt_len + max_new + 16,
                       kv_quant=kv_quant, decode_steps_per_tick=k,
                       prefill_chunk=max(512, args.prompt_len * args.batch))
    engine = ServingEngine(model, params, rt)
    sched = Scheduler(engine)
    rng = np.random.RandomState(0)
    for _ in range(args.batch):
        sched.submit(rng.randint(1, cfg.vocab_size,
                                 (args.prompt_len,)).tolist(),
                     max_new_tokens=max_new)
    # warm until every submission is admitted and decoding (compiles the
    # prefill buckets + the k-step block program off the clock)
    while sched.waiting or sched._prefill_group:
        sched.tick()
    sched.tick()
    sched._drain_inflight()
    # replicate tick()'s page preallocation so the traced block pays no
    # host-side growth, then capture exactly one fused dispatch
    for req in list(sched.running):
        if req in sched.running:
            need = min(len(req.all_tokens) + k + 1,
                       len(req.prompt) + req.max_new_tokens)
            sched._ensure_or_preempt(req, need)
    jax.block_until_ready(engine.cache.lengths)
    logdir = args.out or tempfile.mkdtemp(prefix="serving_block_trace_")
    jax.profiler.start_trace(logdir)
    sched._decode_block(k)
    jax.block_until_ready(sched._inflight[-1][1])
    jax.profiler.stop_trace()
    sched.run_until_done(max_ticks=10 ** 6)
    return _report(logdir, args.top)


def _profile_longctx(args, model, params, kv_quant: str) -> int:
    """Trace the long-context lane (ISSUE 20): one seq-parallel prefill
    chunk dispatch (ring attention over the seq axis, K/V scattered into
    the paged pool) plus one fused decode block beside it — the two
    programs a tick pays while a long prompt streams through the lane.
    Warmed end to end first (a full long prefill + decode) so both
    programs are compiled off the clock."""
    import jax
    import numpy as np

    from butterfly_tpu.core.config import MeshConfig, RuntimeConfig
    from butterfly_tpu.core.mesh import make_mesh
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    n_dev = jax.device_count()
    if n_dev < 4 or n_dev % 4:
        print(f"--long-context needs a device count divisible by 4 for "
              f"the seq=4 mesh (have {n_dev})", file=sys.stderr)
        return 1
    mesh = make_mesh(MeshConfig(seq=4, data=n_dev // 4))
    cfg = model.cfg
    k = args.steps_per_tick
    chunk = args.prompt_len            # per-shard work unit per dispatch
    long_len = 8 * chunk               # the lane's admission regime
    max_new = max(args.max_new, 8 * k + 16)
    rt = RuntimeConfig(max_batch_size=args.batch,
                       max_seq_len=long_len + max_new + 16,
                       kv_quant=kv_quant, decode_steps_per_tick=k,
                       prefill_chunk=chunk,
                       seq_parallel_threshold=long_len // 2)
    engine = ServingEngine(model, params, rt, mesh=mesh)
    if not engine.supports_seq_parallel:
        print("engine cannot seq-parallel on this mesh", file=sys.stderr)
        return 1
    sched = Scheduler(engine)
    rng = np.random.RandomState(0)

    def prompt(n):
        return rng.randint(1, cfg.vocab_size, (n,)).tolist()

    # warm: one long prefill end to end + decoders that keep decoding
    # (compiles the SP chunk program and the k-step block off the clock)
    warm_long = sched.submit(prompt(long_len), max_new_tokens=2)
    for _ in range(args.batch - 1):
        sched.submit(prompt(args.prompt_len), max_new_tokens=max_new)
    while (sched.waiting or sched._prefill_group or sched._sp_group
           or not warm_long.done):
        sched.tick()
    sched._drain_inflight()
    # a fresh long prompt into the (now free) lane slot
    sched.submit(prompt(long_len), max_new_tokens=2)
    sched._sp_admit()
    assert sched._sp_group, "long prompt did not enter the SP lane"
    # replicate tick()'s page preallocation so the traced block pays no
    # host-side growth
    for req in list(sched.running):
        if req in sched.running:
            need = min(len(req.all_tokens) + k + 1,
                       len(req.prompt) + req.max_new_tokens)
            sched._ensure_or_preempt(req, need)
    jax.block_until_ready(engine.cache.lengths)
    logdir = args.out or tempfile.mkdtemp(prefix="longctx_trace_")
    jax.profiler.start_trace(logdir)
    sched._sp_prefill_step()           # ONE seq-parallel chunk dispatch
    sched._decode_block(k)             # one fused block beside the lane
    jax.block_until_ready(sched._inflight[-1][1])
    jax.profiler.stop_trace()
    sched.run_until_done(max_ticks=10 ** 6)
    return _report(logdir, args.top)


def _profile_spec_block(args, model, params, kv_quant: str) -> int:
    """Trace ONE batched speculative block (ISSUE 9): a speculating
    Scheduler is warmed through real admissions until every slot
    decodes — prompts seeded with each request's own greedy
    continuation so prompt-lookup drafts land — then a single
    `--steps-per-tick`-round spec block is dispatched inside the trace
    window: the draft gathers, the [S, gamma+1] verify forwards, and
    the on-device accept/rollback one tick() pays for."""
    import jax
    import numpy as np

    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    k = args.steps_per_tick
    gamma = args.gamma
    cfg = model.cfg
    # budget: warmup rounds PLUS the traced block's worst case
    # (k rounds x gamma+1 emissions per slot)
    max_new = max(args.max_new, 3 * k * (gamma + 1) + 8)
    rt = RuntimeConfig(max_batch_size=args.batch,
                       max_seq_len=args.prompt_len + max_new + gamma + 16,
                       kv_quant=kv_quant, decode_steps_per_tick=k,
                       speculative_gamma=gamma,
                       draft_model=args.draft_source,
                       draft_layers=args.draft_layers,
                       spec_tree_width=getattr(args, "tree_width", 0),
                       spec_tree_nodes=getattr(args, "tree_nodes", 0),
                       prefill_chunk=max(512, args.prompt_len * args.batch))
    rng = np.random.RandomState(0)
    # harvest greedy continuations with a plain scheduler so the traced
    # workload is draft-friendly (looping structure for prompt lookup)
    probe = Scheduler(ServingEngine(model, params,
                                    rt.replace(speculative_gamma=0)))
    half = max(1, args.prompt_len // 2)
    bases = [rng.randint(1, cfg.vocab_size, (half,)).tolist()
             for _ in range(args.batch)]
    cont = [probe.submit(b, max_new_tokens=args.prompt_len - half)
            for b in bases]
    probe.run_until_done(max_ticks=10 ** 6)
    prompts = [b + r.output for b, r in zip(bases, cont)]

    engine = ServingEngine(model, params, rt)
    sched = Scheduler(engine)
    for p in prompts:
        sched.submit(p, max_new_tokens=max_new)
    # warm until every submission is admitted and speculating (compiles
    # the prefill buckets + the spec block program off the clock)
    while sched.waiting or sched._prefill_group:
        sched.tick()
    sched.tick()
    sched._drain_inflight()
    # replicate tick()'s page preallocation so the traced block pays no
    # host-side growth, then capture exactly one fused spec dispatch
    # (tree mode: emit width D+1 per round plus the N-(D+1) compaction
    # overhang — same arithmetic as Scheduler.tick)
    step = k * engine.spec_emit_width
    slack = 0
    if engine.spec_tree_mode:
        slack = engine.spec_tree_geometry[1] - engine.spec_emit_width
    for req in list(sched.running):
        if req in sched.running:
            need = min(len(req.all_tokens) + step + slack + 1,
                       len(req.prompt) + req.max_new_tokens + slack)
            sched._ensure_or_preempt(req, need)
    jax.block_until_ready(engine.cache.lengths)
    logdir = args.out or tempfile.mkdtemp(prefix="spec_block_trace_")
    jax.profiler.start_trace(logdir)
    sched._spec_block(k)
    jax.block_until_ready(sched._inflight[-1][2][0])
    jax.profiler.stop_trace()
    sched.run_until_done(max_ticks=10 ** 6)
    return _report(logdir, args.top)


def _profile_pipeline(args, model, params, kv_quant: str) -> int:
    """Trace TWO chained in-flight decode blocks (ISSUE 5 dispatch-
    ahead): after warmup, block 1 is dispatched and block 2 is chained
    on its device-resident carry WITHOUT draining block 1 — both land
    inside the trace window, so the timeline shows whether the device
    runs them back-to-back (the host work between the two dispatches
    hides under block 1's compute) or leaves a bubble."""
    import jax
    import numpy as np

    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    k = args.steps_per_tick
    cfg = model.cfg
    # budget for warmup (first token + one drained block) PLUS the two
    # traced in-flight blocks — otherwise the second dispatch is a
    # no-op once the device-side budgets are spent
    max_new = max(args.max_new, 3 * k + 8)
    rt = RuntimeConfig(max_batch_size=args.batch,
                       max_seq_len=args.prompt_len + max_new + 16,
                       kv_quant=kv_quant, decode_steps_per_tick=k,
                       inflight_blocks=2,
                       prefill_chunk=max(512, args.prompt_len * args.batch))
    engine = ServingEngine(model, params, rt)
    sched = Scheduler(engine)
    rng = np.random.RandomState(0)
    for _ in range(args.batch):
        sched.submit(rng.randint(1, cfg.vocab_size,
                                 (args.prompt_len,)).tolist(),
                     max_new_tokens=max_new)
    # warm until every submission decodes (compiles the prefill buckets
    # and the k-step block program off the clock), then reconcile
    while sched.waiting or sched._prefill_group:
        sched.tick()
    sched.tick()
    sched._drain_inflight()
    # preallocate pages for BOTH blocks so neither dispatch pays
    # host-side growth inside the window (tick()'s (m+1)*k+1 horizon)
    for req in list(sched.running):
        if req in sched.running:
            need = min(len(req.all_tokens) + 2 * k + 2,
                       len(req.prompt) + req.max_new_tokens)
            sched._ensure_or_preempt(req, need)
    jax.block_until_ready(engine.cache.lengths)
    logdir = args.out or tempfile.mkdtemp(prefix="pipeline_trace_")
    jax.profiler.start_trace(logdir)
    sched._decode_block(k)   # block 1
    sched._decode_block(k)   # block 2, chained on block 1's carry
    jax.block_until_ready(sched._inflight[-1][1])
    jax.profiler.stop_trace()
    sched.run_until_done(max_ticks=10 ** 6)
    return _report(logdir, args.top)


def _profile_prefill_batch(args, model, params, kv_quant: str) -> int:
    """Trace ONE batched prefill dispatch (ISSUE 4): the [B, Tbucket]
    gang-admission program is compiled off the clock by a warmup batch,
    then a fresh gang of B waiting requests is admitted inside the trace
    window — exactly one engine.prefill_batch dispatch, including the
    pool scatters and the per-row start/length masking."""
    import jax
    import numpy as np

    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine.serving import ServingEngine
    from butterfly_tpu.sched.scheduler import Scheduler

    cfg = model.cfg
    B = max(1, min(args.prefill_max_batch, args.batch))
    # prefill_chunk sized so the whole gang's prompts fit one round:
    # the traced window then holds ONE [B, Tbucket] dispatch
    rt = RuntimeConfig(max_batch_size=args.batch,
                       max_seq_len=args.prompt_len + args.max_new + 16,
                       kv_quant=kv_quant, prefill_max_batch=B,
                       prefill_chunk=max(512, args.prompt_len * B))
    engine = ServingEngine(model, params, rt)
    sched = Scheduler(engine)
    rng = np.random.RandomState(0)

    def prompt():
        return rng.randint(1, cfg.vocab_size, (args.prompt_len,)).tolist()

    # warmup gang: compiles the (B-bucket, T-bucket) prefill program
    # (and the decode program the post-trace drain uses) off the clock
    for _ in range(B):
        sched.submit(prompt(), max_new_tokens=2)
    sched.run_until_done()
    for _ in range(B):
        sched.submit(prompt(), max_new_tokens=2)
    jax.block_until_ready(engine.cache.lengths)
    logdir = args.out or tempfile.mkdtemp(prefix="prefill_batch_trace_")
    jax.profiler.start_trace(logdir)
    sched._admit()  # ONE gang admission: the batched prefill dispatch
    jax.block_until_ready(engine.cache.k_pages)
    jax.profiler.stop_trace()
    sched.run_until_done(max_ticks=10 ** 6)
    return _report(logdir, args.top)


def _report(logdir: str, top: int) -> int:
    print(f"# trace: {logdir}", file=sys.stderr)
    planes = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if not planes:
        print("no xplane captured", file=sys.stderr)
        return 1
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        print("xprof not installed: raw trace kept at the path above, "
              "no hlo_stats table", file=sys.stderr)
        return 1
    data, _ = raw_to_tool_data.xspace_to_tool_data(planes, "hlo_stats", {})
    rows = json.loads(data) if isinstance(data, (str, bytes)) else data
    _print_hlo_stats(rows, top)
    return 0


def _print_hlo_stats(rows, top: int) -> None:
    """hlo_stats arrives as a GViz-style table; print top ops by self time."""
    if isinstance(rows, dict) and "rows" in rows:   # gviz DataTable json
        cols = [c.get("label", c.get("id", "")) for c in rows["table"]["cols"]] \
            if "table" in rows else [c.get("label", c.get("id", ""))
                                     for c in rows["cols"]]
        raw = rows["rows"] if "rows" in rows else rows["table"]["rows"]
        recs = [{cols[i]: (c or {}).get("v") for i, c in enumerate(r["c"])}
                for r in raw]
    elif isinstance(rows, list):
        recs = rows
    else:
        print(json.dumps(rows)[:2000])
        return
    tkey = next((k for k in recs[0] if "self" in k.lower()
                 and "time" in k.lower() and "%" not in k), None)
    if tkey is None:
        tkey = next(k for k in recs[0] if "time" in k.lower())
    recs.sort(key=lambda r: -(r.get(tkey) or 0))
    tot = sum(r.get(tkey) or 0 for r in recs)
    print(f"{'self_time':>12} {'%':>6}  op")
    for r in recs[:top]:
        name = (r.get("HLO Op Name") or r.get("hlo_op_name")
                or r.get("HLO Op Expression") or "?")
        cat = r.get("HLO Op Category") or r.get("hlo_category") or ""
        t = r.get(tkey) or 0
        print(f"{t:12.1f} {100*t/max(tot,1e-9):6.2f}  [{cat}] {str(name)[:110]}")


if __name__ == "__main__":
    sys.exit(main())
