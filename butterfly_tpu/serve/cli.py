"""`butterfly` CLI: the reference's planned client-facing entrypoints
(/root/reference/CLAUDE.md:23; BASELINE.json north_star names
`butterfly serve` / `generate`).

    butterfly generate --model gpt2-124m --prompt "hello" --max-new 32
    butterfly serve    --model llama3-8b --port 8000
    butterfly route    --backends 10.0.0.1:8000,10.0.0.2:8000
    butterfly workload generate|replay   (workload subsystem)
    butterfly lint     [paths...]   (project-native static analysis)

Models load from --ckpt (HF safetensors dir or our sharded checkpoint);
without --ckpt, weights are random-initialized (smoke/demo mode).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="butterfly",
                                description="Butterfly-TPU inference CLI")
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--model", default="tiny",
                        help="preset name (gpt2-124m, llama3-8b, llama3-70b, "
                             "mixtral-8x7b, ...), 'tiny', or 'tiny-<arch>' "
                             "for a family's toy (tiny-trinity)")
        sp.add_argument("--ckpt", default=None, help="checkpoint path")
        sp.add_argument("--tokenizer", default=None)
        sp.add_argument("--dtype", default=None, help="override compute dtype")
        sp.add_argument("--tensor-parallel", type=int, default=1)
        sp.add_argument("--stage-parallel", type=int, default=1)
        sp.add_argument("--expert-parallel", type=int, default=1)
        sp.add_argument("--data-parallel", type=int, default=1)
        sp.add_argument("--seq-parallel", type=int, default=1,
                        help="sequence/context parallelism: shard the "
                             "prompt over N devices (the long-context "
                             "path — prefix KV stays sharded where it "
                             "was computed)")
        sp.add_argument("--seq-impl", choices=["ring", "ulysses"],
                        default="ring",
                        help="sequence-parallel attention: 'ring' "
                             "(ppermute K/V rotation, no head-count "
                             "constraint) or 'ulysses' (all_to_all "
                             "head<->sequence reshard; needs heads "
                             "divisible by / replicable over the axis)")
        sp.add_argument("--max-seq", type=int, default=2048)
        sp.add_argument("--dcn-axes", default="data",
                        help="comma list of mesh axes to place ACROSS TPU "
                             "slices (DCN) on multi-slice jobs; all other "
                             "axes stay within a slice on ICI "
                             "(e.g. 'data' or 'data,stage')")
        sp.add_argument("--quant", choices=["none", "int8"], default="none",
                        help="weight-only quantization (int8 halves the "
                             "HBM bytes the decode loop streams)")

    def kv_quant_flag(sp):
        sp.add_argument("--kv-quant", choices=["none", "int8"],
                        default="none",
                        help="KV-cache quantization (int8 halves the cache "
                             "bytes — the dominant decode-loop term at "
                             "serving batch sizes; applies to both the "
                             "contiguous and the paged serving cache)")

    g = sub.add_parser("generate", help="one-shot text generation")
    common(g)
    kv_quant_flag(g)
    g.add_argument("--prompt", default="Hello")
    g.add_argument("--max-new", type=int, default=64)
    g.add_argument("--temperature", type=float, default=0.0)
    g.add_argument("--top-k", type=int, default=0)
    g.add_argument("--top-p", type=float, default=1.0)
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--speculate", type=int, default=0, metavar="GAMMA",
                   help="prompt-lookup speculative decoding: draft GAMMA "
                        "tokens per step, verify in one forward. Greedy "
                        "output is identical to plain decode; with "
                        "--temperature > 0 the rejection-sampling "
                        "correction keeps the output distribution exact")

    s = sub.add_parser("serve", help="HTTP serving with continuous batching")
    common(s)
    kv_quant_flag(s)
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--host", default="0.0.0.0")
    s.add_argument("--max-batch", type=int, default=8)
    s.add_argument("--page-size", type=int, default=16)
    s.add_argument("--num-pages", type=int, default=0,
                   help="pages of the KV pool under the page table (0: "
                        "max-batch x max-seq / page-size, room for every "
                        "slot at max-seq): a deployment whose streams are "
                        "mostly shorter than max-seq holds fewer, and a "
                        "request that finds none left is preempted")
    s.add_argument("--top-k", type=int, default=0,
                   help="serving-wide top-k sampling filter")
    s.add_argument("--top-p", type=float, default=1.0)
    s.add_argument("--max-queue", type=int, default=256)
    s.add_argument("--no-trace", action="store_true",
                   help="disable per-request tracing (GET /debug/requests "
                        "then reports enabled=false); tracing is on by "
                        "default and costs one ring-buffer append per "
                        "scheduling event")
    s.add_argument("--role", choices=["prefill", "decode", "both"],
                   default="both",
                   help="fleet placement role advertised on /health: the "
                        "disaggregated control plane (`butterfly route "
                        "--disaggregate`) sends prefill-heavy requests to "
                        "'prefill' replicas and generation to 'decode' "
                        "ones. Advisory — the replica serves whatever it "
                        "is sent; 'both' (default) joins both tiers")
    s.add_argument("--prefix-caching", action="store_true",
                   help="reuse KV pages across requests sharing a prompt "
                        "prefix (content-hashed, refcounted; cuts TTFT for "
                        "shared system prompts)")
    s.add_argument("--host-tier-mb", type=float, default=0.0,
                   help="host-RAM KV tier budget in MiB (requires "
                        "--prefix-caching): device-pool evictions demote "
                        "pages to host memory instead of dropping them, "
                        "and a later prefix hit on an evicted chain "
                        "revives the pages back to device — TTFT of a "
                        "warm hit at host-RAM prices. 0 (default) = off")
    s.add_argument("--host-tier-dir", default=None, metavar="DIR",
                   help="optional disk-spill directory for the host KV "
                        "tier: pages LRU-demoted past --host-tier-mb "
                        "spill to .npz files here instead of being "
                        "dropped (a third tier below host RAM)")
    s.add_argument("--speculate", type=int, default=0, metavar="GAMMA",
                   help="serving-path speculative decoding on the block "
                        "pipeline: draft GAMMA tokens per slot from the "
                        "device-side token history, verify ALL slots in "
                        "one batched (GAMMA+1)-token forward per round, "
                        "accept/rollback on device. Sampling-safe "
                        "(rejection-sampling correction keeps "
                        "temperature/top-k/top-p requests exact); "
                        "clients opt out per request with "
                        '"speculative": false')
    def positive_int(v):
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {n}")
        return n

    s.add_argument("--decode-steps-per-tick", type=positive_int, default=1,
                   help="fused block width: this many decode iterations "
                        "(or, with --speculate, draft+verify+accept "
                        "rounds) run per scheduler tick inside ONE "
                        "jitted scan (on-device sampling, RNG, and EOS "
                        "masking), drained in ONE stacked fetch. Raise "
                        "to amortize per-token host overhead (tokens "
                        "then surface in bursts)")
    s.add_argument("--prefill-max-batch", type=positive_int, default=8,
                   help="how many one-token requests the warm-up submits "
                        "as a burst before the server listens. Nothing "
                        "else reads it since the batched prefill "
                        "dispatch it capped went: a burst of arrivals "
                        "rides the fused blocks' chunks, bounded by "
                        "prefill_inline_budget a step")
    s.add_argument("--seq-parallel-threshold", type=int, default=0,
                   help="long-context admission lane: prompts LONGER "
                        "than this many tokens prefill through chunked "
                        "seq-parallel dispatches sharded over the "
                        "mesh's seq axis (requires --seq-parallel > 1), "
                        "landing their KV in the ordinary paged pool — "
                        "prefix-cache-visible and decoded like any "
                        "other slot. 0 (default) = off")
    s.add_argument("--seq-parallel-chunk", type=int, default=0,
                   help="tokens per seq-parallel prefill dispatch "
                        "(rounded up to a multiple of the seq degree); "
                        "0 = auto: seq degree x prefill_chunk, so the "
                        "per-device chunk share matches the ordinary "
                        "prefill budget and decode ITL interference "
                        "stays within the same bound")
    def slo_flags(sp):
        sp.add_argument("--slo-ttft-ms", type=float, default=None,
                        help="declared time-to-first-token objective in "
                             "milliseconds: per-request attainment is "
                             "recorded into the slo_ttft_ok_total / "
                             "slo_violations_total{kind} counters and "
                             "the rolling slo_burn_rate gauge (unset = "
                             "no SLO accounting)")
        sp.add_argument("--slo-itl-ms", type=float, default=None,
                        help="declared mean inter-token-latency "
                             "objective in milliseconds (per finished "
                             "request, the streaming rate a client "
                             "experiences); recorded like --slo-ttft-ms")

    slo_flags(s)
    s.add_argument("--profiler-port", type=int, default=0,
                   help="start the on-demand XProf profiler server on "
                        "this port (0 = off): TensorBoard/XProf can "
                        "then trigger captures of the live replica. "
                        "ImportError/port-in-use degrade to a logged "
                        "warning, never a crash. POST /debug/profile "
                        "{duration_ms} captures a duration-bounded "
                        "trace of the live tick loop either way")
    s.add_argument("--flightrec-dir", default=None, metavar="DIR",
                   help="write anomaly flight-recorder post-mortem "
                        "artifacts (JSON) here when a trigger fires "
                        "(SLO burn, preemption storm, deadline-expiry "
                        "burst, wedge latch); unset keeps them "
                        "in-memory at GET /debug/flightrecorder only")
    s.add_argument("--inflight-blocks", type=positive_int, default=2,
                   help="decode blocks kept in flight on the device "
                        "(dispatch-ahead): block t+1 chains on block "
                        "t's device-resident carry before t is "
                        "drained, so host scheduling overlaps device "
                        "compute. 1 = the synchronous drain-every-tick "
                        "loop; the starvation clock (starved_s of "
                        "GET /debug/ticks, the device_bubble_seconds "
                        "histogram) shows how long the device waited "
                        "for the host before each launch, and "
                        "starved_cause whether a deeper queue would "
                        "have helped (exposed, late_tick) or a full "
                        "barrier drained it whatever the depth")
    s.add_argument("--timeseries-interval", type=float, default=1.0,
                   metavar="SECONDS",
                   help="periodic signal-history sampling interval for "
                        "GET /debug/timeseries (the bounded ring "
                        "tools/dashboard.py renders; alert rules note "
                        "threshold crossings into the flight "
                        "recorder). 0 disables the recorder entirely "
                        "(zero extra per-tick host work)")

    # multi-replica router: fronts N `butterfly serve` replicas with
    # prefix-affinity routing + health-aware failover (router/). Loads no
    # model and touches no accelerator — deliberately NOT given the
    # common() model/mesh flags.
    r = sub.add_parser("route",
                       help="route requests across serve replicas "
                            "(prefix-affinity + health-aware failover)")
    r.add_argument("--backends", required=True,
                   help="comma-separated replica addresses, e.g. "
                        "10.0.0.1:8000,10.0.0.2:8000")
    r.add_argument("--port", type=int, default=8100)
    r.add_argument("--host", default="0.0.0.0")
    r.add_argument("--page-size", type=int, default=16,
                   help="MUST match the replicas' --page-size: affinity "
                        "keys hash the same token blocks their prefix "
                        "caches key pages by")
    r.add_argument("--affinity-blocks", type=int, default=4,
                   help="leading full prompt blocks hashed into the "
                        "affinity key (requests agreeing on this many "
                        "blocks share a replica)")
    r.add_argument("--saturate-after", type=int, default=8,
                   help="outstanding requests at which the affinity "
                        "target is considered saturated and routing "
                        "falls back to least-outstanding")
    r.add_argument("--probe-interval", type=float, default=0.5,
                   help="seconds between /health probes of each replica")
    r.add_argument("--dead-after", type=int, default=3,
                   help="consecutive connect failures before a replica "
                        "is marked dead (re-probed with jittered "
                        "exponential backoff)")
    r.add_argument("--read-timeout", type=float, default=300.0,
                   help="per-request socket timeout toward a replica")
    r.add_argument("--disaggregate", action="store_true",
                   help="run the KV-aware fleet control plane instead of "
                        "the plain router: prefill-heavy requests go to "
                        "--role prefill replicas, their KV pages stream "
                        "to a --role decode replica by chain hash "
                        "(GET /kv/pages -> POST /kv/import), and "
                        "generation finishes there; GET /fleet/state "
                        "exposes the placement table")
    r.add_argument("--disagg-threshold", type=int, default=64,
                   help="predicted fresh-prefill tokens at which a "
                        "request is worth the prefill/decode handoff "
                        "(below it, requests dispatch directly to the "
                        "decode tier)")
    slo_flags(r)  # control-plane SLO accounting for disaggregated
    # requests (fleet_slo_* counters + burn rate; measured across the
    # whole handoff, the latency the CLIENT experiences)

    # local disaggregated fleet for manual debugging: N prefill + M
    # decode in-process replicas behind one control plane, all tiny-
    # model loopback — the same harness the fleet soak tests drive.
    f = sub.add_parser("fleet",
                       help="spin a local prefill/decode fleet (replicas "
                            "+ control plane, in-process) for manual "
                            "debugging")
    f.add_argument("--topology", default="2p2d",
                   help="'<N>p<M>d' = N prefill + M decode replicas "
                        "(default 2p2d), or a bare count for a "
                        "role-less pool")
    f.add_argument("--page-size", type=int, default=8)
    f.add_argument("--max-batch", type=int, default=2)
    f.add_argument("--max-seq", type=int, default=128)
    f.add_argument("--disagg-threshold", type=int, default=16)
    f.add_argument("--autoscale", action="store_true",
                   help="run the closed-loop autoscaler (fleet/"
                        "autoscale.py) on every tier in the topology: "
                        "scraped queue-depth ring history grows a "
                        "saturated tier (warm-before-join) and shrinks "
                        "an idle one (drain-before-retire), "
                        "independently per tier; decisions land in "
                        "GET /debug/flightrecorder")
    f.add_argument("--scale-min", type=int, default=1,
                   help="autoscaler floor per tier (default 1)")
    f.add_argument("--scale-max", type=int, default=4,
                   help="autoscaler ceiling per tier (default 4)")
    f.add_argument("--scale-high", type=float, default=4.0,
                   help="tier-mean queue_depth above which a tier "
                        "grows (default 4.0)")
    f.add_argument("--scale-low", type=float, default=0.5,
                   help="tier-mean queue_depth below which a tier "
                        "shrinks, after the hysteresis cooldown "
                        "(default 0.5)")
    f.add_argument("--host-tier-mb", type=float, default=0.0,
                   help="per-replica host-RAM KV tier budget in MiB "
                        "(see `serve --host-tier-mb`); 0 = off")
    f.add_argument("--host-tier-dir", default=None, metavar="DIR",
                   help="disk-spill directory for the replicas' host "
                        "KV tiers (see `serve --host-tier-dir`)")
    f.add_argument("--chaos", default=None, metavar="PLAN",
                   help="seeded fault-injection plan: a JSON file "
                        '({"seed": N, "faults": [{"kind": "delay|error|'
                        'wedge|drop|truncate|slow_stream", "target": '
                        '"prefill|decode:0|*", "endpoint": "/generate", '
                        '"p": 0.3, "count": 5}, ...]}) or the literal '
                        "'default' for the stock soak plan "
                        "(fleet/chaos.py). Faults inject at the replica "
                        "HTTP fronts and the control plane's handoff "
                        "legs, deterministically per seed")
    slo_flags(f)  # declared objectives activate SLO accounting AND
    # SLO-aware admission shedding on every in-process replica

    # workload subsystem (butterfly_tpu/workload/): generate seeded
    # stochastic traffic traces and replay them open-loop at a live URL.
    w = sub.add_parser("workload",
                       help="stochastic workload tooling: generate a "
                            "seeded trace, or replay one at a server "
                            "open-loop")
    wsub = w.add_subparsers(dest="wcmd", required=True)

    def workload_shape_flags(sp, for_generate=True):
        if for_generate:
            sp.add_argument("--workload", default="mixed_chat",
                            help="canned workload name "
                                 "(mixed_chat, uniform)")
            sp.add_argument("--n", type=int, default=32,
                            help="requests to sample")
            sp.add_argument("--seed", type=int, default=0)
            sp.add_argument("--arrival", default="poisson:8",
                            help="arrival process: poisson:<rate>, "
                                 "burst:<rate_on>:<mean_on_s>:"
                                 "<mean_off_s>[:<rate_off>], "
                                 "ramp:<r0>:<r1>:<ramp_s>")
            sp.add_argument("--vocab", type=int, default=258,
                            help="token-id vocabulary (match the "
                                 "target model; 258 = tiny)")
            sp.add_argument("--page-size", type=int, default=16,
                            help="prefix alignment unit — match the "
                                 "server's --page-size")
            sp.add_argument("--prompt-lo", type=int, default=32)
            sp.add_argument("--prompt-hi", type=int, default=1024)
            sp.add_argument("--max-new-lo", type=int, default=8)
            sp.add_argument("--max-new-hi", type=int, default=256)
            sp.add_argument("--deadline-ms", type=float, default=None,
                            help="latency budget for the workload's "
                                 "deadline-carrying cohort")

    wg = wsub.add_parser("generate",
                         help="sample a workload + arrival schedule "
                              "into a JSONL trace")
    workload_shape_flags(wg)
    wg.add_argument("--out", required=True, metavar="FILE",
                    help="trace output path (JSONL)")

    wr = wsub.add_parser("replay",
                         help="fire a saved trace at a live server/"
                              "router URL with absolute-time fidelity "
                              "(open loop)")
    wr.add_argument("--trace", required=True, metavar="FILE")
    wr.add_argument("--url", required=True,
                    help="target base URL, e.g. http://127.0.0.1:8000")
    wr.add_argument("--speed", type=float, default=1.0,
                    help="schedule compression: 2.0 replays twice as "
                         "fast")
    wr.add_argument("--timeout", type=float, default=120.0)
    wr.add_argument("--slo-ttft-ms", type=float, default=None)
    wr.add_argument("--slo-itl-ms", type=float, default=None)

    # project-native static analysis (tools/staticcheck.py, ISSUE 11):
    # the donation/lock/host-sync/determinism contracts as AST rules —
    # the same walk the tier-1 test runs.
    li = sub.add_parser("lint",
                        help="AST lint for the serving contracts "
                             "(donation, locks, host-sync, HTTP "
                             "timeouts, determinism, PRNG hygiene); "
                             "exit 1 on any unsuppressed finding")
    li.add_argument("paths", nargs="*",
                    help="files/trees to lint (default: butterfly_tpu "
                         "tools tests, fixture snippets excluded)")
    li.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog (id, slug, scope, "
                         "invariant) and exit")
    li.add_argument("--json", action="store_true",
                    help="machine-readable jsonl findings")
    li.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed findings")
    li.add_argument("--force", action="store_true",
                    help="ignore per-rule scopes (ad-hoc sweeps)")

    # timeseries dashboard renderer (tools/dashboard.py, ISSUE 16):
    # stdlib-only like `lint` — loads no model, touches no accelerator.
    d = sub.add_parser("dash",
                       help="render a dumped /debug/timeseries or "
                            "/fleet/timeseries body as a static HTML "
                            "dashboard (SVG sparklines, alert "
                            "annotations) or --text sparklines")
    d.add_argument("dump", help="JSON file (the timeseries body)")
    d.add_argument("--out", default=None,
                   help="write HTML here (default: stdout)")
    d.add_argument("--text", action="store_true",
                   help="unicode sparklines for terminals instead of "
                        "HTML")
    return p


def resolve_model(args):
    from butterfly_tpu.core.config import PRESETS, tiny
    from butterfly_tpu.models.common import Model
    if args.model in PRESETS:
        cfg = PRESETS[args.model]()
    elif args.model == "tiny" or args.model.startswith("tiny-"):
        # "tiny-<arch>": the toy of a family (core/config.py tiny)
        cfg = tiny(args.model[5:] or "llama", dtype="float32",
                   param_dtype="float32")
    else:
        raise KeyError(f"no model {args.model!r}: a preset "
                       f"({', '.join(sorted(PRESETS))}), 'tiny' or "
                       "'tiny-<arch>'")
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    if getattr(args, "expert_parallel", 1) > 1 and cfg.routed:
        # EP means GShard all_to_all dispatch, not an expert-sharded
        # dense MoE where every expert still computes every token.
        cfg = cfg.replace(moe_impl="ep")
    return Model(cfg)


def load_params(model, args, mesh=None):
    """Weights in the form the engines hold them: --quant applied, laid
    out over `mesh`. Without --ckpt they are random, and every leaf is
    built directly in that form (quant/int8.py init_params_by_leaf): the
    float tree of an 8B model is twice one chip's memory, and a tree
    built on one device and then sharded is a whole copy on that device.
    """
    import jax
    quant = getattr(args, "quant", "none")
    if not args.ckpt:
        from butterfly_tpu.quant.int8 import init_params_by_leaf
        # btf: disable=BTF006 demo mode: no-ckpt random-init weights are deliberately identical across runs
        return init_params_by_leaf(model.cfg, jax.random.PRNGKey(0),
                                   quant=quant, mesh=mesh)
    from butterfly_tpu.ckpt import load_checkpoint
    params = load_checkpoint(args.ckpt, model.cfg)
    if quant == "int8":
        from butterfly_tpu.quant import quantize_int8
        params = quantize_int8(params, model.cfg)
    return shard_for_mesh(params, model.cfg, mesh)


def build_mesh(args):
    """Mesh from the CLI parallelism flags; None when all are 1.

    Multi-host: call with BUTTERFLY_NUM_PROCESSES set and the coordinator
    flags in the environment — init_distributed runs first so
    jax.devices() spans every host (core/mesh.py).
    """
    import jax
    from butterfly_tpu.core.config import MeshConfig
    from butterfly_tpu.core.mesh import init_distributed, make_hybrid_mesh

    tp = getattr(args, "tensor_parallel", 1)
    pp = getattr(args, "stage_parallel", 1)
    ep = getattr(args, "expert_parallel", 1)
    dp = getattr(args, "data_parallel", 1)
    sq = getattr(args, "seq_parallel", 1)
    n = tp * pp * ep * dp * sq
    if n == 1:
        return None
    init_distributed()
    ndev = len(jax.devices())
    if n > ndev:
        raise SystemExit(
            f"error: --tensor-parallel {tp} x --stage-parallel {pp} x "
            f"--expert-parallel {ep} x --data-parallel {dp} x "
            f"--seq-parallel {sq} = {n} devices, "
            f"but only {ndev} are available")
    cfg = MeshConfig(data=dp, stage=pp, expert=ep, seq=sq, tensor=tp)
    # hybrid: on a multi-slice job the --dcn-axes span slices over DCN
    # and every per-layer collective stays on ICI; single-slice device
    # sets (and CPU) fall back to the plain mesh inside
    dcn = tuple(a for a in getattr(args, "dcn_axes", "data").split(",") if a)
    try:
        return make_hybrid_mesh(cfg, jax.devices()[:n], dcn_axes=dcn)
    except ValueError as e:
        raise SystemExit(f"error: {e}")


def shard_for_mesh(params, cfg, mesh):
    if mesh is None:
        return params
    from butterfly_tpu.quant import shard_quantized_params, tree_is_quantized
    if tree_is_quantized(params):
        return shard_quantized_params(params, cfg, mesh)
    from butterfly_tpu.parallel.partition import shard_params
    return shard_params(params, cfg, mesh)


def _device_line(engine) -> str:
    """What a run ran on: the device as JAX reports it, and which
    kernels the engine's programs hold (ops.record_kernels)."""
    from butterfly_tpu.core.mesh import device_report
    dev = device_report()
    return (f"[butterfly] platform={dev['platform']} "
            f"device_kind={dev['kind']!r} devices={dev['count']} "
            f"kernels={engine.kernel_mode} "
            f"kernel_calls={json.dumps(engine.kernel_calls, sort_keys=True)}")


def cmd_generate(args) -> int:
    from butterfly_tpu.core.config import RuntimeConfig
    from butterfly_tpu.engine import InferenceEngine, SamplingParams
    from butterfly_tpu.utils.tokenizer import load_tokenizer

    model = resolve_model(args)
    tok = load_tokenizer(args.tokenizer or args.ckpt)
    mesh = build_mesh(args)
    params = load_params(model, args, mesh)
    engine = InferenceEngine(
        model, params,
        runtime=RuntimeConfig(max_seq_len=args.max_seq,
                              kv_quant=args.kv_quant),
        mesh=mesh)
    vocab = model.cfg.vocab_size
    stop = tok.eos_id if tok.eos_id is not None and tok.eos_id < vocab else -1
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, max_new_tokens=args.max_new,
                        stop_token=stop)
    ids = tok.encode(args.prompt)
    bad = [i for i in ids if i >= vocab]
    if bad:
        print(f"error: tokenizer produced ids {bad[:5]} outside the model's "
              f"vocab ({vocab}); pass a matching --tokenizer", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    if args.seq_parallel > 1:
        if args.speculate > 0:
            print("error: --speculate does not compose with "
                  "--seq-parallel (the long-context path has no warm "
                  "multi-token verify)", file=sys.stderr)
            return 2
        # long-context path: sp_forward prefill + sp_decode_step loop
        # (engine.generate_long docs); --kv-quant int8 composes — the
        # seq-parallel cache shards int8 codes + scales and the ring
        # kernel dequantizes per block
        res = engine.generate_long(ids, sp, seed=args.seed,
                                   impl=args.seq_impl)
        dt = time.perf_counter() - t0
        n = int(res.lengths[0])
        print(tok.decode(res.tokens[0, :n].tolist()))
        print(f"[butterfly] {n} tokens in {dt:.2f}s over "
              f"{args.seq_parallel}-way sequence parallelism", file=sys.stderr)
        print(_device_line(engine), file=sys.stderr)
        return 0
    if args.speculate > 0:
        try:
            res = engine.generate_speculative(ids, sp, gamma=args.speculate,
                                              seed=args.seed)
        except NotImplementedError as e:  # e.g. data/stage-parallel mesh
            print(f"error: {e}", file=sys.stderr)
            return 2
        dt = time.perf_counter() - t0
        n = len(res.tokens)
        text = tok.decode(res.tokens.tolist())
        print(text)
        print(f"[butterfly] {n} tokens in {dt:.2f}s via {res.forwards} "
              f"forwards ({res.tokens_per_forward:.2f} tok/forward, "
              f"{res.accepted_drafts} drafts accepted)", file=sys.stderr)
        print(_device_line(engine), file=sys.stderr)
        return 0
    res = engine.generate([ids], sp, seed=args.seed)
    dt = time.perf_counter() - t0
    n = int(res.lengths[0])
    text = tok.decode(res.tokens[0, :n].tolist())
    print(text)
    print(f"[butterfly] {n} tokens in {dt:.2f}s "
          f"({n / dt:.1f} tok/s incl. compile)", file=sys.stderr)
    print(_device_line(engine), file=sys.stderr)
    return 0


def cmd_serve(args) -> int:
    if getattr(args, "seq_parallel_threshold", 0) > 0 \
            and args.seq_parallel <= 1:
        print("error: --seq-parallel-threshold needs a seq axis — pass "
              "--seq-parallel N (> 1) to shard long prompts over N "
              "devices", file=sys.stderr)
        return 2
    from butterfly_tpu.serve.server import run_server
    return run_server(args)


def cmd_route(args) -> int:
    backends = [b for b in args.backends.split(",") if b.strip()]
    if args.disaggregate:
        from butterfly_tpu.fleet.controlplane import fleet_forever
        return fleet_forever(backends, host=args.host, port=args.port,
                             page_size=args.page_size,
                             affinity_blocks=args.affinity_blocks,
                             saturate_after=args.saturate_after,
                             probe_interval=args.probe_interval,
                             dead_after=args.dead_after,
                             read_timeout=args.read_timeout,
                             disagg_threshold=args.disagg_threshold,
                             slo_ttft_s=(args.slo_ttft_ms / 1e3
                                         if args.slo_ttft_ms else None),
                             slo_itl_s=(args.slo_itl_ms / 1e3
                                        if args.slo_itl_ms else None))
    if args.slo_ttft_ms or args.slo_itl_ms:
        print("[butterfly] note: --slo-ttft-ms/--slo-itl-ms apply to "
              "the control plane (--disaggregate) and to the replicas' "
              "own `serve` flags; the plain router records no SLO",
              file=sys.stderr)
    from butterfly_tpu.router.proxy import route_forever
    return route_forever(backends, host=args.host, port=args.port,
                         page_size=args.page_size,
                         affinity_blocks=args.affinity_blocks,
                         saturate_after=args.saturate_after,
                         probe_interval=args.probe_interval,
                         dead_after=args.dead_after,
                         read_timeout=args.read_timeout)


def cmd_fleet(args) -> int:
    """`butterfly fleet`: the in-process soak topology, held open for
    manual poking (curl the printed control-plane URL)."""
    from butterfly_tpu.fleet.harness import start_fleet

    chaos = None
    if getattr(args, "chaos", None):
        from butterfly_tpu.fleet.chaos import ChaosPlan, default_plan
        chaos = default_plan() if args.chaos == "default" \
            else ChaosPlan.from_file(args.chaos)
        print(f"[butterfly] chaos plan armed: {len(chaos.rules)} rules, "
              f"seed {chaos.seed}", flush=True)
    print(f"[butterfly] starting local fleet {args.topology} "
          f"(tiny model, warming each replica)...", flush=True)
    slo_ttft = getattr(args, "slo_ttft_ms", None)
    slo_itl = getattr(args, "slo_itl_ms", None)
    fleet = start_fleet(args.topology, page_size=args.page_size,
                        max_batch=args.max_batch, max_seq=args.max_seq,
                        disagg_threshold=args.disagg_threshold,
                        chaos=chaos,
                        host_kv_tier_mb=getattr(args, "host_tier_mb", 0.0),
                        host_kv_tier_dir=getattr(args, "host_tier_dir",
                                                 None),
                        slo_ttft_s=slo_ttft / 1e3 if slo_ttft else None,
                        slo_itl_s=slo_itl / 1e3 if slo_itl else None)
    scaler = None
    if getattr(args, "autoscale", False):
        from butterfly_tpu.fleet.autoscale import Autoscaler, TierPolicy
        from butterfly_tpu.fleet.harness import parse_topology
        policies = [TierPolicy(role, min_replicas=args.scale_min,
                               max_replicas=args.scale_max,
                               high=args.scale_high, low=args.scale_low)
                    for role in dict.fromkeys(parse_topology(args.topology))]
        scaler = Autoscaler(fleet.state, fleet.spawn, fleet.retire,
                            policies, interval_s=1.0)
        scaler.start()
        print(f"[butterfly] autoscaler live on "
              f"{[p.role for p in policies]} "
              f"(bounds {args.scale_min}..{args.scale_max}, band "
              f"{args.scale_low}..{args.scale_high}; decisions at "
              f"GET /debug/flightrecorder)", flush=True)
    print(f"[butterfly] control plane: {fleet.url}  "
          f"(GET /fleet/state, POST /generate)", flush=True)
    for r in fleet.replicas:
        print(f"[butterfly]   replica {r.rid}  role={r.role}", flush=True)
    print("[butterfly] Ctrl-C to stop", flush=True)
    try:
        import threading
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        if scaler is not None:
            scaler.stop()
        fleet.stop()
    return 0


def cmd_workload(args) -> int:
    """`butterfly workload generate|replay` (ISSUE 10): the seeded
    traffic-modeling subsystem's CLI surface, stdlib-fast (no
    engine)."""
    from butterfly_tpu.workload import (assign_arrivals, get_workload,
                                        parse_arrival)
    from butterfly_tpu.workload import replay as replay_mod

    if args.wcmd == "generate":
        wl = get_workload(args.workload, page_size=args.page_size,
                          vocab=args.vocab, prompt_lo=args.prompt_lo,
                          prompt_hi=args.prompt_hi,
                          max_new_lo=args.max_new_lo,
                          max_new_hi=args.max_new_hi,
                          deadline_ms=args.deadline_ms)
        specs = wl.sample(args.n, args.seed)
        assign_arrivals(specs, parse_arrival(args.arrival), args.seed)
        replay_mod.save_trace(args.out, specs, workload=wl,
                              arrival=args.arrival, seed=args.seed)
        cohorts = {}
        for s in specs:
            cohorts[s.cohort] = cohorts.get(s.cohort, 0) + 1
        print(json.dumps({
            "trace": str(args.out), "workload": wl.name, "n": len(specs),
            "seed": args.seed, "arrival": args.arrival,
            "cohorts": cohorts,
            "prompt_tokens": sum(len(s.tokens) for s in specs),
            "max_new_tokens": sum(s.max_new for s in specs),
            "span_s": round(specs[-1].arrival_s, 3) if specs else 0.0}))
        return 0
    # replay
    _, specs = replay_mod.load_trace(args.trace)
    stats = replay_mod.replay_trace(
        args.url, specs, speed=args.speed, timeout=args.timeout,
        slo_ttft_ms=args.slo_ttft_ms, slo_itl_ms=args.slo_itl_ms)
    print(json.dumps(stats, indent=2))
    # like loadgen: sheds/504s are requested backpressure; only
    # transport errors / 5xx faults fail the replay
    return 0 if stats["outcomes"]["error"] == 0 else 1


def cmd_lint(args) -> int:
    """`butterfly lint`: the project-native static analyzer
    (tools/staticcheck.py) from the package entrypoint. The analyzer
    lives with the repo's tooling, not inside the wheel — a source
    checkout is where the contracts it enforces exist."""
    import importlib
    from pathlib import Path

    tools = Path(__file__).resolve().parent.parent.parent / "tools"
    if not (tools / "staticcheck.py").exists():
        print("error: butterfly lint needs the repo's tools/ directory "
              "(run from a source checkout)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(tools))
    try:
        staticcheck = importlib.import_module("staticcheck")
    finally:
        sys.path.remove(str(tools))
    argv = list(args.paths)
    if args.list_rules:
        argv.append("--list-rules")
    if args.json:
        argv.append("--json")
    if args.show_suppressed:
        argv.append("--show-suppressed")
    if args.force:
        argv.append("--force")
    return staticcheck.main(argv)


def cmd_dash(args) -> int:
    """`butterfly dash`: the stdlib timeseries dashboard renderer
    (tools/dashboard.py) from the package entrypoint — same source-
    checkout contract as `butterfly lint`."""
    import importlib
    from pathlib import Path

    tools = Path(__file__).resolve().parent.parent.parent / "tools"
    if not (tools / "dashboard.py").exists():
        print("error: butterfly dash needs the repo's tools/ directory "
              "(run from a source checkout)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(tools))
    try:
        dashboard = importlib.import_module("dashboard")
    finally:
        sys.path.remove(str(tools))
    argv = [args.dump]
    if args.out:
        argv += ["--out", args.out]
    if args.text:
        argv.append("--text")
    return dashboard.main(argv)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd in ("generate", "serve", "fleet"):
        # the commands that compile; route/lint/dash never import jax
        from butterfly_tpu.core.compile_cache import place_compile_cache
        place_compile_cache()
    return {"generate": cmd_generate, "serve": cmd_serve,
            "route": cmd_route,
            "fleet": cmd_fleet, "workload": cmd_workload,
            "lint": cmd_lint, "dash": cmd_dash}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
