#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 servebench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. It starts `butterfly serve` as a child (servebench/launcher.py),
drives the cell's traffic at it over HTTP as clients would, and prints as
the last line of its output one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` and, with --trace 1, `breakdown`. With
--trace 0 the metrics are the cell's end-to-end metrics; with --trace 1
its per-layer metrics, read by the files in servebench/layer_metrics/.
An earlier line (`{"thirds": ...}`) gives each end-to-end metric over
each third of the window.

This process never imports JAX: the load generator shares no interpreter
lock with the server's tick loop, and the server is the one process that
holds the chip. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits with a code other than 0.
`--rehearsal` (tests only) runs the same path on the CPU backend and
never prints a device metric.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import math       # noqa: E402
import os         # noqa: E402
import subprocess  # noqa: E402
import sys        # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from servebench import metrics as M           # noqa: E402
from servebench.client import LoadGenerator   # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.launcher import NO_CHIP       # noqa: E402
from servebench.server import Server, ServerFailed  # noqa: E402
from servebench.traffic import load_traffic, make_plan  # noqa: E402

#: the traced run's device trace: the window's last seconds, so that the
#: profiler's export, which stalls the server's tick loop, falls after it
TRACE_S = 3.0
#: sources a rehearsal on the CPU may not print
DEVICE_SOURCES = ("device_trace",)
PROGRAM = "butterfly_tpu/serve/cli.py"


def say(msg: str) -> None:
    print(f"[servebench +{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def cache_entries() -> int:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    try:
        return len(os.listdir(d))
    except OSError:
        return 0


class Poller(threading.Thread):
    """Traced run only: follows /debug/ticks (a ring of 512 ticks)
    while the window is open."""

    def __init__(self, server: Server):
        super().__init__(daemon=True)
        self.server, self.ticks, self.stop_ev = server, [], threading.Event()
        self.since = None

    def poll(self) -> None:
        path = "/debug/ticks" + (f"?since={self.since}"
                                 if self.since is not None else "")
        try:
            d = self.server.get(path, timeout=10)
        except OSError:
            return
        self.ticks.extend(d.get("ticks", []))
        self.since = d.get("next_seq", self.since)

    def run(self) -> None:
        while not self.stop_ev.wait(2.0):
            self.poll()


def profile(server: Server, logdir: Path, at: float, out: dict) -> None:
    """POST /debug/profile when the clock reads `at`."""
    time.sleep(max(0.0, at - time.monotonic()))
    out["at"] = time.monotonic()
    try:
        out["result"] = server.post(
            "/debug/profile", {"duration_ms": TRACE_S * 1e3,
                               "logdir": str(logdir)}, timeout=TRACE_S + 60)
    except OSError as e:
        out["error"] = f"{type(e).__name__}: {e}"


def trace_written(logdir: Path) -> bool:
    """The profiler has written its `.xplane.pb` (the server's handler
    gives up on a long export, and the tick thread that writes it is a
    daemon: the server must not be stopped before the file is there)."""
    sizes = {str(f): f.stat().st_size for f in logdir.rglob("*.xplane.pb")}
    steady = bool(sizes) and sizes == _trace_sizes.get(str(logdir))
    _trace_sizes[str(logdir)] = sizes
    return steady


#: sizes of the trace files at the last look: written means there and no
#: longer growing
_trace_sizes: dict = {}


def reduce_trace(logdir: Path, out: Path) -> dict:
    """The trace's summary, from a child on the CPU backend (after the
    server has gone: reading a trace imports JAX)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "servebench" / "xplane.py"),
                        str(logdir), "--out", str(out)], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        raise RuntimeError("trace reduction failed: " + r.stderr[-2000:])
    return json.loads(out.read_text())


def run_refcheck(cell, seed, env, out_dir: Path, require_tpu: int) -> dict:
    """servebench/refcheck.py as a child; its result, or why there is none."""
    out = out_dir / "refcheck.json"
    cmd = [sys.executable, str(ROOT / "servebench" / "refcheck.py"),
           "--config", str(cell.config_path), "--seed", str(seed),
           "--out", str(out), "--require-tpu", str(require_tpu)]
    with (out_dir / "refcheck.log").open("wb") as log:
        try:
            r = subprocess.run(cmd, cwd=ROOT, env=env, stdout=log,
                               stderr=subprocess.STDOUT, timeout=900)
        except subprocess.TimeoutExpired:
            return {"ok": False, "error": "timed out"}
    if r.returncode == NO_CHIP:
        return {"ok": False, "no_chip": True}
    if r.returncode != 0 or not out.exists():
        return {"ok": False, "error": f"exit code {r.returncode}; see "
                                      f"{out_dir / 'refcheck.log'}"}
    return json.loads(out.read_text())


def check_streams(streams, vocab: int) -> list:
    """What is wrong with the answers, as a list of sentences."""
    bad = []
    for s in streams:
        if s.failed:
            bad.append(f"{s.rid}: {s.failed}")
        elif s.finished and len(s.tokens) != s.asked:
            bad.append(f"{s.rid}: {len(s.tokens)} tokens, asked {s.asked}")
        elif len(s.tokens) > s.asked:
            bad.append(f"{s.rid}: more tokens than asked")
        elif any(t < 0 or t >= vocab for t in s.tokens):
            bad.append(f"{s.rid}: token id outside the vocabulary")
    return bad


def check_health(health: dict, config: dict, rehearsal: bool) -> list:
    bad = []
    if health.get("status") != "ok":
        return [f"/health says {health}"]
    if rehearsal:
        return bad
    k = health.get("kernels", {})
    calls = k.get("calls", {})
    if k.get("mode") != "compiled":
        bad.append(f"kernels are {k.get('mode')!r}, not compiled")
    if any(c.endswith(":interpret") for c in calls):
        bad.append("a kernel ran interpreted")
    if "dense_fallback" in calls and not config.get("dense_fallback_allowed"):
        bad.append("a layer took the dense path where a kernel belongs")
    for name in config.get("kernels_must_hold", []):
        if f"{name}:compiled" not in calls:
            bad.append(f"no program holds the {name} kernel")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: run on the CPU backend; no device "
                         "metric is printed")
    args = ap.parse_args()
    if not (ROOT / PROGRAM).exists():
        print(f"servebench: {PROGRAM} is not in this checkout: the benchmark "
              "drives the repository's own `butterfly serve`", file=sys.stderr)
        return 2
    manifest = load_manifest(ROOT)
    cell = Cell(manifest, args.workload, ROOT)
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])
    traced = bool(args.trace)
    config = cell.config
    traffic = load_traffic(cell.traffic_path)
    out_dir = ROOT / "chiprun_out" / "servebench" / \
        f"{cell.name}-s{args.seed}-t{args.trace}"
    out_dir.mkdir(parents=True, exist_ok=True)

    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={cell.chips}")
    require_tpu = 0 if args.rehearsal else cell.chips
    refcheck = None
    if traced:
        # the comparison with the plain reference: a child that holds the
        # chip before the server does, in the traced run only (it adds
        # about a minute of set-up; see PERF.md)
        refcheck = run_refcheck(cell, args.seed, env, out_dir, require_tpu)
        if refcheck.get("no_chip"):
            print(f"servebench: cell {cell.name} needs {cell.chips} TPU "
                  "chip(s). Nothing was measured.", file=sys.stderr)
            return 2
        say(f"reference check: {refcheck}")
    server = Server(cell.config_path, out_dir / "server.log", env,
                    require_tpu=require_tpu)
    gen = None
    rc = None
    try:
        server.start()
        say(f"{cell.name}: server starting (log {server.log})")
        line = server.wait_ready(1100)
        say(line)
        health = server.get("/health")
        dev = health["device"]
        if not args.rehearsal and (dev["platform"] != "tpu"
                                   or dev["count"] < cell.chips):
            print(f"servebench: cell {cell.name} needs {cell.chips} TPU "
                  f"chip(s); JAX found platform {dev['platform']!r}, "
                  f"{dev['count']} device(s). Nothing was measured.",
                  file=sys.stderr)
            return 2
        plan = make_plan(traffic, args.seed, seconds, config["vocab_size"],
                         config["serve"]["max_seq"])
        gen = LoadGenerator("127.0.0.1", server.port, plan)
        poller = Poller(server) if traced else None
        prof: dict = {}
        gen.start()
        if plan.lead_finished:
            # the window opens on a steady server: when enough requests
            # have finished that as many leave as are let in
            gen.run(until=gen.t_zero + plan.lead_max_s,
                    done=lambda: sum(s.finished for s in gen.streams)
                    >= plan.lead_finished)
            w0 = time.monotonic()
        else:
            w0 = gen.t_zero + plan.lead_s
        w1 = w0 + seconds
        prof_thread = None
        if traced:
            prof_thread = threading.Thread(
                target=profile, daemon=True,
                args=(server, out_dir / "trace", w1 - TRACE_S - 0.2, prof))
            prof_thread.start()
        gen.run(until=w0)
        setup_s = w0 - T_START
        n_cache0 = cache_entries()
        if poller:
            poller.poll()
            poller.start()
        say(f"window open: set-up {setup_s:.1f}s, {len(gen.live)} streams live")
        gen.run(until=w1)
        n_cache1 = cache_entries()
        if plan.kind == "open" and plan.grace_s > 0:
            def firsts_in():
                return all(s.times or s.failed for s in gen.streams
                           if s.due is not None and w0 <= s.due < w1)
            gen.run(until=w1 + plan.grace_s, stop_sending_at=w1,
                    done=firsts_in)
        say(f"window closed: {len(gen.streams)} requests sent")
        if prof_thread is not None:
            # the capture ends with the window; its export holds the
            # tick loop for some seconds more
            until = time.monotonic() + 180
            while time.monotonic() < until and (
                    prof_thread.is_alive()
                    or not trace_written(out_dir / "trace")):
                gen.run(until=time.monotonic() + 0.5, stop_sending_at=w1)
        if poller:
            poller.stop_ev.set()
            poller.join(timeout=15)
            poller.poll()
        health = server.get("/health")
        requests = server.get("/debug/requests") if traced else {}
        exhausted = gen.exhausted
        streams = gen.streams
        gen.stop()
        gen = None
        rc = server.stop()
        say(f"server stopped, exit code {rc}")
    except ServerFailed as e:
        print(f"servebench: {e}", file=sys.stderr)
        return 2 if server.proc.returncode == NO_CHIP else 3
    finally:
        if gen is not None:
            gen.stop()
        if rc is None:
            server.stop()

    # -- correctness ---------------------------------------------------------
    problems = check_streams(streams, config["vocab_size"])
    problems += check_health(health, config, args.rehearsal)
    if rc != 0:
        problems.append(f"server exit code {rc} after SIGTERM")
    if refcheck is not None and not refcheck.get("ok"):
        problems.append(f"the model disagrees with its plain reference: {refcheck}")
    late = [(s.sent - s.due) * 1e3 for s in streams
            if s.due is not None and s.sent is not None]
    late_p99 = M.percentile(late, 99) if late else 0.0
    if late_p99 > plan.late_limit_ms:
        problems.append(f"the generator ran {late_p99:.1f} ms late (p99), over "
                        f"the limit of {plan.late_limit_ms} ms")
    if exhausted:
        problems.append("a closed-loop client ran out of planned requests")
    if plan.kind == "burst" and not any(
            not s.failed and (not s.times or s.times[0] >= w1) for s in streams):
        problems.append("the batch ran out before the window closed: every "
                        "request had started; the traffic file needs more rounds")
    if not any(s.finished for s in streams):
        problems.append("no request finished")
    for p in problems[:20]:
        say("NOT CORRECT: " + p)

    # -- metrics -------------------------------------------------------------
    e2e_names = [m["name"] for m in cell.end_to_end]
    info = {"thirds": M.thirds(streams, w0, w1, e2e_names), "samples": {},
            "whole": {}, "compiled_in_window": n_cache1 - n_cache0,
            "gen_late_p99_ms": late_p99, "seed": args.seed,
            "refcheck": refcheck,
            "workload": cell.name, "seconds": seconds}
    values = {}
    for m in cell.end_to_end:
        if m["name"] == "setup_s":
            values["setup_s"] = setup_s
        elif m["name"] in M.END_TO_END:
            v, n = M.END_TO_END[m["name"]](streams, w0, w1)
            info["samples"][m["name"]] = n
            if v is not None:
                values[m["name"]] = v
    info["whole"] = dict(values)
    mem = [d.get("peak_bytes_in_use", 0) for d in dev_memory(health)]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": max(mem, default=0)}
    result = {"correct": not problems, "attempted": len(streams),
              "failed": sum(1 for s in streams if s.failed)}
    if traced:
        trace = {}
        if "error" in prof:
            # the handler gives the capture 30 s beyond its length and then
            # answers 501; the export of four chips' trace takes longer, but
            # the file is whole once the server has exited
            say(f"profile: {prof['error']}")
        if not args.rehearsal:
            try:
                trace = reduce_trace(out_dir / "trace", out_dir / "trace.json")
            except (RuntimeError, OSError, subprocess.TimeoutExpired) as e:
                say(f"trace: {e}")
        # what a per-layer reader may read; it returns a number, or None if
        # there is nothing to read
        ctx = SimpleNamespace(streams=streams, w0=w0, w1=w1, seconds=seconds,
                      ticks=poller.ticks, requests=requests,
                      trace=trace,
                      config=config, traffic=traffic, health=health,
                      device=device, chips=cell.chips, late_ms=late,
                      ready_line=line, info=info,
                      trace_at=prof.get("at", w1 - TRACE_S) + TRACE_S / 2,
                      wall_minus_mono=time.time() - time.monotonic())
        (out_dir / "ticks.json").write_text(json.dumps(poller.ticks))
        (out_dir / "requests.json").write_text(json.dumps(requests))
        per_layer = {}
        for m in cell.per_layer:
            if args.rehearsal and m["source"] in DEVICE_SOURCES:
                continue
            v = cell.reader(m["name"])(ctx)
            if v is not None and not (isinstance(v, float) and math.isnan(v)):
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = per_layer
        if trace.get("busy_s"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s, _ in trace["ops"][:10]],
                "idle_gaps": [[n, s] for n, s in trace["idle_gaps"][:10]]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": (v if not math.isinf(v) else 1e12),
                                 "unit": units[k]} for k, v in values.items()}
    result["device"] = device
    print(json.dumps(info), flush=True)
    print(json.dumps(result), flush=True)
    return 0


def dev_memory(health: dict) -> list:
    return [m for m in health.get("device", {}).get("memory", []) if m]


if __name__ == "__main__":
    sys.exit(main())
