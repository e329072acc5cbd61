#!/usr/bin/env python
"""Chip benchmark: one JSON line with the headline metric.

Headline: steady-state decode throughput (tokens/sec/chip) for the
BASELINE.json configs[1] model of record — Llama-3-8B geometry — in int8
(weights + KV cache) on the available chip(s). `vs_baseline` is the
ratio to the first 8B run (bench_baseline.json key "tpu_8b" — the
reference is an unimplemented scaffold with no published numbers,
BASELINE.md); it carries no signal across a change of headline model.
The trend metrics are the physical ones: `hbm_util` / `mfu` (roofline
fractions against the peaks in obs/benchmark.py) and the mixed-workload
serving fields (`mixed_serving_tokens_per_sec`, `mixed_ttft_*`,
`mixed_itl_req_mean_*`, `mixed_serving_preemptions`, the
operating-point table) — see docs/observability.md §benchmark-json.

The same line also carries the PRODUCT serving-path numbers: Scheduler +
ServingEngine + paged Pallas kernel + int8 KV pools under staggered
arrivals — serving tokens/sec/chip and TTFT/ITL percentiles, the
BASELINE.md metrics of record.

Every rate here is a device metric, so this program needs a chip: with
no accelerator it exits non-zero and prints no result (ROADMAP A0
replaces it with a benchmark of cells).
"""
import json
import sys
from pathlib import Path

BASELINE_FILE = Path(__file__).parent / "bench_baseline.json"


def lint_preflight():
    """Run the project static analyzer (tools/staticcheck.py, ISSUE 11)
    over the default trees; returns the unsuppressed findings. A bench
    JSON published from a tree that violates the donation/lock/
    host-sync/determinism contracts would certify numbers the serving
    path can't be trusted to have produced — main() refuses."""
    tools = str(Path(__file__).parent / "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import staticcheck
    return staticcheck.run_default()


def main() -> int:
    lint = lint_preflight()
    if lint:
        print("bench: refusing to run on a tree with unsuppressed "
              "staticcheck findings:", file=sys.stderr)
        for f in lint:
            print("  " + f.render(), file=sys.stderr)
        return 2
    import jax
    from butterfly_tpu.core.config import llama3_8b
    from butterfly_tpu.models.common import Model
    from butterfly_tpu.obs.benchmark import (run_autoscale_benchmark,
                                             run_chaos_benchmark,
                                             run_decode_benchmark,
                                             run_fleet_benchmark,
                                             run_longctx_benchmark,
                                             run_mixed_benchmark,
                                             run_serving_benchmark,
                                             run_spec_benchmark,
                                             run_warm_prefill_benchmark)
    from butterfly_tpu.core.compile_cache import place_compile_cache
    from butterfly_tpu.obs.benchmark import require_chip
    from butterfly_tpu.quant.int8 import init_params_by_leaf

    require_chip("bench.py")
    place_compile_cache()

    # Llama-3-8B geometry (BASELINE configs[1]): int8 weights ~8.5 GB
    # fit one v5e chip's 16 GiB HBM with the int8 KV cache.
    cfg = llama3_8b().replace(max_seq_len=2048)
    batch, prompt_len, max_new = 128, 128, 128
    # decode_steps_per_tick=16: each tick runs 16 decode iterations as
    # ONE fused jitted scan (engine._decode_scan) — one dispatch and one
    # stacked token fetch per tick, so the per-token host work
    # (dispatch, operand conversion, RNG split) and the host<->device
    # round trip are paid once per block, not per token.
    # prefill_max_batch=16: a burst's prompts gang-prefill as
    # [B, 128] dispatches instead of one prompt per tick — the TTFT
    # lever this config's staggered-arrival phase measures
    serving_kw = dict(n_requests=64, prompt_len=128, max_new=128,
                      max_batch=32, decode_steps_per_tick=16,
                      prefill_max_batch=16)
    baseline_key = "tpu_8b"

    model = Model(cfg)
    # int8 weight-only quant: the serving default for the bandwidth-bound
    # decode loop (CLI --quant int8), built by the same leaf-at-a-time
    # initializer as the CLI's no-checkpoint path, so the 8B float tree
    # never materializes. Every leaf is born in the compute dtype, so
    # neither benchmark engine's cast_params donates the shared tree.
    params = init_params_by_leaf(cfg, jax.random.PRNGKey(0), quant="int8")
    # int8 KV cache + write-combined decode window (CLI --kv-quant int8):
    # halves the cache bytes — the dominant decode-loop term at this
    # batch — and amortizes the whole-pool copy each in-loop cache
    # update costs on TPU (models/common.py window docs).
    kv_quant = "int8"
    stats = run_decode_benchmark(model, params, batch=batch,
                                 prompt_len=prompt_len, max_new=max_new,
                                 kv_quant=kv_quant)
    # Serving path at BOTH dispatch-ahead depths, same operating point:
    # inflight_blocks=1 is the synchronous drain-every-tick loop (the
    # "before"), the default depth keeps blocks in flight so host
    # scheduling overlaps device compute (the "after"). The headline
    # serving_* keys come from the pipelined run; the synchronous run's
    # throughput/gap ride along under a _sync suffix so the JSON line
    # carries the before/after comparison directly.
    serving_sync = run_serving_benchmark(
        model, params, kv_quant=kv_quant,
        inflight_blocks=1,
        isolated_decode_tok_s_chip=stats["decode_tokens_per_sec_per_chip"],
        **serving_kw)
    # Write-combined KV window off (ISSUE 12): same operating point with
    # per-token pool scatters, so the JSON line carries the on/off pair
    # (`_nowin` suffix, serving_gap style). Greedy outputs are
    # byte-identical in both modes (parity grid).
    serving_nowin = run_serving_benchmark(
        model, params, kv_quant=kv_quant,
        kv_write_combine=False,
        isolated_decode_tok_s_chip=stats["decode_tokens_per_sec_per_chip"],
        **serving_kw)
    serving = run_serving_benchmark(
        model, params, kv_quant=kv_quant,
        # serving_gap (serving / isolated tok/s/chip) rides the serving
        # JSON so the trajectory tracks the gap this path is closing
        isolated_decode_tok_s_chip=stats["decode_tokens_per_sec_per_chip"],
        **serving_kw)
    for k in ("serving_tokens_per_sec_per_chip",
              "serving_capacity_tokens_per_sec", "serving_gap"):
        if k in serving_sync:
            serving[k + "_sync"] = serving_sync[k]
        if k in serving_nowin:
            serving[k + "_nowin"] = serving_nowin[k]
    # Speculation phase (ISSUE 9): spec-on vs spec-off tok/s at the
    # round's operating point plus the speculation instruments —
    # spec_tokens_per_forward (> 1 = drafts landing), the accept rate,
    # and drain barriers per verify round (~0 = the spec rounds really
    # pipeline instead of barriering like the old host accept loop).
    # Draft-friendly workload (prompts seeded with the model's own
    # greedy continuation) so prompt lookup has something to mine.
    # Warm-prefix flash prefill phase (ISSUE 13): long prompts (>= 512)
    # prefilled in chunks, so every chunk after the first runs the warm
    # path and admission rounds mix warm continuations with fresh
    # arrivals. On/off pair at the same operating point rides the JSON
    # under the `_dense` suffix (the `_nowin` pattern): off = the dense
    # O(T*S) warm fallback + the gang-freshness split this PR retires.
    # warm_prefill_kernelized says whether the on leg took the kernel.
    serving.update(run_warm_prefill_benchmark(
        model, params, kv_quant=kv_quant, prompt_len=640,
        prefill_chunk=256, n_requests=6, max_batch=4))
    # Long-context phase (ISSUE 20): one prompt spanning >= 8 prefill
    # chunks admitted through the scheduler's seq-parallel lane
    # (chunked SP prefill -> paged decode), beside short decoders. The
    # acceptance pair: longctx_mixed_itl_p95 vs the alone p95 + the
    # declared one-SP-chunk budget (longctx_itl_within_budget), plus
    # the ring-vs-jnp microbench pair (longctx_ring_kernelized says
    # whether the first leg was the Pallas kernel).
    serving.update(run_longctx_benchmark(
        model, params, prompt_len=4096, prefill_chunk=512, max_new=16,
        decode_new=64, kv_quant="int8"))
    # The spec phase also drafts with BOTH sources (ngram vs the real
    # on-device draft model, ISSUE 14) on mixed_chat-shaped prompts at
    # the same operating point: spec_accept_rate_model >
    # spec_accept_rate_ngram is the ROADMAP item 3 evidence key.
    # draft_layers=1: a 1-layer shared-embed draft is the cheapest
    # resident draft on the 8B (the operating point can raise it from a
    # profile).
    spec_kw = dict(n_requests=serving_kw["n_requests"],
                   prompt_len=serving_kw["prompt_len"],
                   max_new=serving_kw["max_new"],
                   max_batch=serving_kw["max_batch"],
                   decode_steps_per_tick=serving_kw["decode_steps_per_tick"],
                   gamma=4, draft_layers=1)
    serving.update(run_spec_benchmark(
        model, params, kv_quant=kv_quant, **spec_kw))
    # Mixed-workload phase (ISSUE 10): the canned mixed_chat population
    # (heterogeneous prompts 32-1024 on TPU, shared-prefix cohorts,
    # priority/deadline mix) fired OPEN-LOOP in bursts against a page
    # pool sized below worst-case demand, so preemption, SLO-aware
    # shedding, deadline scrubbing, and the prefix cache are all
    # measured instead of idle (the uniform phase above reports
    # serving_preemptions: 0 by construction). Also emits the
    # decode_steps_per_tick x inflight_blocks operating-point table +
    # knee — the curve the round's operating point is chosen from.
    # pool at 15% of worst-case demand: the cohort mix averages
    # ~18 pages/request, so 32 contested slots (~576 pages) overrun
    # the ~390-page pool while the largest single request (81
    # pages) still fits — preemption measured, not configured away
    # host KV tier (ISSUE 17): the contested pool above evicts
    # shared-prefix chains mid-run; a 64 MB host tier turns those
    # into demotions that revive on the cohorts' next admission —
    # kv_tier_hit_rate/restore latency measured under real pressure
    mixed_kw = dict(n_requests=64, max_batch=32,
                    prompt_lo=32, prompt_hi=1024,
                    max_new_lo=16, max_new_hi=256, page_size=16,
                    pool_fraction=0.15, host_kv_tier_mb=64.0,
                    decode_steps_per_tick=16, inflight_blocks=2,
                    prefill_max_batch=16, kv_quant="int8",
                    grid=[(4, 1), (4, 2), (16, 1), (16, 2)])
    serving.update(run_mixed_benchmark(model, params, **mixed_kw))
    # Unified mixed dispatch (ISSUE 18) acceptance pair as explicit
    # deltas: admission barrier count (fused ≈ 0 vs the alternating
    # reference's one per mid-flight arrival) and the ITL-p95 change
    # that buys at heavy prompt load (negative = fused improves the
    # tail). The raw `_alt` pairs ride along from the benchmark fns.
    for phase, itl, bar in (
            ("serving", "itl_req_mean_p95", "serving_admission_barriers"),
            ("mixed", "mixed_itl_req_mean_p95", "mixed_admission_barriers")):
        if itl in serving and itl + "_alt" in serving:
            serving[phase + "_itl_p95_delta"] = \
                serving[itl] - serving[itl + "_alt"]
        if bar in serving and bar + "_alt" in serving:
            serving[phase + "_admission_barriers_delta"] = \
                serving[bar] - serving[bar + "_alt"]
    toks_per_sec_chip = stats["tokens_per_sec_per_chip"]

    vs = 1.0
    if BASELINE_FILE.exists():
        base = json.loads(BASELINE_FILE.read_text())
        if base.get(baseline_key):
            vs = toks_per_sec_chip / base[baseline_key]

    out = {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(toks_per_sec_chip, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs, 4),
        "model": "llama3-8b",
        "quant": "int8",
        "kv_quant": kv_quant,
        "decode_isolated_tokens_per_sec_per_chip":
            round(stats["decode_tokens_per_sec_per_chip"], 2),
        "hbm_util": round(stats["hbm_util"], 4),
        "mfu": round(stats["mfu"], 4),
        # the preflight refused above unless this is 0: the trajectory
        # records the tree staying contract-clean round over round
        "staticcheck_findings_total": len(lint),
    }
    for k, v in serving.items():
        out[k] = round(v, 4) if isinstance(v, float) else v
    # Fleet tier: a 2-prefill + 2-decode disaggregated topology
    # (in-process, tiny model — the fleet numbers
    # measure the control plane's handoff + rolling drain/restart, not
    # the model) driven through the loadgen soak. Carries the before/
    # after TTFT (direct vs disaggregated), the cross-replica KV
    # transfer volume/hit-rate, and the zero-drop soak property.
    fleet = run_fleet_benchmark("2p2d")
    for k, v in fleet.items():
        out[k] = round(v, 4) if isinstance(v, float) else v
    # Chaos tier: the same 2p2d topology under the seeded stock fault
    # plan (delays, 500s, a breaker-tripping wedge burst, drops,
    # truncations) plus a spent-deadline burst. Carries the overload-
    # protection counters (serving_shed_total, deadline_expired_total,
    # breaker_open_total) and the terminal-outcome property: every
    # request ends in tokens, 429, or 504 — zero hangs, zero silent
    # drops (chaos_unterminal/chaos_errors == 0 when healthy).
    chaos = run_chaos_benchmark("2p2d")
    for k, v in chaos.items():
        out[k] = round(v, 4) if isinstance(v, float) else v
    # Elastic tier (ISSUE 17): a ramp arrival against a 1-decode floor
    # with the closed-loop autoscaler governing the decode tier.
    # Carries SLO attainment, the replica-seconds integral vs the
    # static peak shape (the saving the loop exists to buy), and the
    # flight-recorder scale-event audit count.
    autoscale = run_autoscale_benchmark("1p1d")
    for k, v in autoscale.items():
        out[k] = round(v, 4) if isinstance(v, float) else v
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
