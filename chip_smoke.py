#!/usr/bin/env python3
"""The quickest proof that `butterfly serve` still starts on the chip.

    python3 chip_smoke.py            (from the root of a checkout, on a
                                      machine with one TPU chip or four)

Drives the serving path once through the entry points a user calls
(`python -m butterfly_tpu.serve.cli serve|generate`, then HTTP), at the
full published width and depth of Llama-3-8B with seeded random
weights, int8 weights and int8 KV, 32 slots of 2048 tokens, fused blocks
of 4 steps (what fits 16 GB: at 8 steps the write-combined window
doubles and the mixed block's program misses by 220 MB — PR 21 run):

  serve     warm, listen, answer requests of different prompt lengths
            over /generate (blocking and SSE) and /v1/completions, each
            with exactly the token count it asked for; /health must be
            `ok` and /metrics must parse at the end; SIGTERM must end
            the server with exit code 0 (1 = serving was wedged);
  generate  the contiguous engine (flash prefill + fused decode),
            32 new tokens;
  kernels   tools/chip_kernels.py: every Pallas variant compiled by
            Mosaic and compared with its jnp reference;
and, on a host that shows four chips, the same `serve` check with
--tensor-parallel 4 (int8, then bf16 weights that fit no single chip:
all four devices must hold a share) and the long-prompt lane
(--seq-parallel 4, the only product path through the ring kernel).

It fails if JAX finds no TPU, if any request fails or comes back short,
if the server latched an error (a device fault inside a tick, or the
watchdog after 60 s without a beat — e.g. a program compiling inside a
tick), if a kernel ran interpreted, or if a program that should hold a
kernel took the dense path. It prints set-up time apart from request
time and no rate: rates belong to the benchmark.

One process holds a chip at a time, so this parent never imports JAX
and each child starts only after the previous one has exited. The last
line of stdout is one JSON object, {"ok": true, "device": {...}} with
the device as JAX reports it; on failure the exit code is not 0.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: the contract allows the one-chip run 1200 s, compilation included;
#: each four-chip phase is one more 8B server to build and warm
BUDGET_S, BUDGET_PER_MESH_PHASE_S = 1150, 360
T0 = time.monotonic()
PLATFORM = "tpu"
MODEL, VOCAB = "llama3-8b", 128256   # core/config.py llama3_8b

SERVE = ["--model", MODEL, "--max-batch", "32", "--max-seq", "2048",
         "--decode-steps-per-tick", "4", "--host", "127.0.0.1"]
INT8 = ["--quant", "int8", "--kv-quant", "int8"]
#: phase -> (flags, kernels the server's programs must hold, may a
#: decode layer take the dense path?). A seq-only mesh cannot shard the
#: paged kernel's operands, so there decode is dense by design.
SERVE_PHASES = {
    "serve": (INT8, ["paged_int8_win"], False),
    "tp4-int8": (INT8 + ["--tensor-parallel", "4"], ["paged_int8_win"],
                 False),
    "tp4-bf16": (["--quant", "none", "--kv-quant", "none",
                  "--tensor-parallel", "4"], ["paged_win"], False),
    # threshold 700: the 900- and 1000-token prompts ride the lane in
    # the 1024-token chunk program the warm-up's long prompt compiled
    "sp4": (INT8 + ["--seq-parallel", "4", "--seq-parallel-threshold",
                    "700"], ["ring_int8"], True),
}
ONE_CHIP = ["serve", "generate", "kernels"]
FOUR_CHIPS = ["tp4-int8", "tp4-bf16", "sp4"]

PROBE = """
import json, jax, jaxlib
try:
    import libtpu
    tpu = libtpu.__version__
except Exception:
    tpu = None
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d), "jax": jax.__version__,
                  "jaxlib": jaxlib.__version__, "libtpu": tpu}))
"""

_children: list = []
_budget = BUDGET_S


class Failed(Exception):
    pass


def left() -> float:
    return _budget - (time.monotonic() - T0)


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def spawn(cmd, log: Path) -> subprocess.Popen:
    """Start one child (its own process group, output to `log`); the
    previous child must be gone — a chip belongs to one process."""
    for p in _children:
        if p.poll() is None:
            raise Failed("internal: previous child still holds the chip")
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=log.open("wb"),
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen, sig=signal.SIGTERM, wait: float = 60):
    """Signal the child's group and reap it; SIGKILL if it lingers."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, sig)
            proc.wait(timeout=wait)
        except (subprocess.TimeoutExpired, ProcessLookupError):
            pass
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    return proc.returncode


def run(cmd, log: Path, timeout: float) -> int:
    proc = spawn(cmd, log)
    try:
        return proc.wait(timeout=max(1.0, min(timeout, left())))
    except subprocess.TimeoutExpired:
        stop(proc, signal.SIGKILL)
        raise Failed(f"timed out: {' '.join(cmd[:6])} ... (log: {log})")


def tail(log: Path, n: int = 25) -> str:
    lines = log.read_text(errors="replace").splitlines()
    return "\n".join("    | " + ln for ln in lines[-n:])


# -- HTTP ------------------------------------------------------------------

def http(port: int, path: str, body=None, timeout: float = 300):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode("utf-8", "replace")


def ask(port: int, kind: str, prompt_len: int, want: int, seed: int):
    """One request; returns (ok, detail). `want` tokens must come back:
    stop_token -1 switches the EOS stop off, random weights can draw it."""
    rng = random.Random(seed)
    tokens = [rng.randrange(1, VOCAB) for _ in range(prompt_len)]
    base = {"max_tokens": want, "temperature": 0.0, "stop_token": -1}
    try:
        if kind == "generate":
            _, raw = http(port, "/generate", {**base, "tokens": tokens})
            got = len(json.loads(raw)["tokens"])
        elif kind == "sse":
            _, raw = http(port, "/generate",
                          {**base, "tokens": tokens, "stream": True})
            events = [ln[5:].strip() for ln in raw.splitlines()
                      if ln.startswith("data:")]
            if not events or events[-1] != "[DONE]":
                return False, f"{kind} len={prompt_len}: stream not [DONE]"
            got = sum("token" in json.loads(e) for e in events[:-1])
        else:
            _, raw = http(port, "/v1/completions",
                          {**base, "prompt": tokens})
            out = json.loads(raw)
            got = out["usage"]["completion_tokens"]
            if out["choices"][0]["finish_reason"] != "length":
                return False, f"{kind} len={prompt_len}: finish_reason " \
                              f"{out['choices'][0]['finish_reason']!r}"
    except (urllib.error.URLError, OSError, ValueError, KeyError) as e:
        return False, f"{kind} len={prompt_len}: {type(e).__name__}: {e}"
    ok = got == want
    return ok, f"{kind} len={prompt_len}: {got}/{want} tokens"


def send_requests(port: int, long_prompt: bool):
    """A few requests one at a time, then a burst in parallel."""
    plan = [("generate", 5, 8), ("sse", 70, 16), ("completions", 300, 12)]
    burst = [("generate", 3, 24), ("sse", 40, 8), ("completions", 129, 16),
             ("generate", 600, 10), ("sse", 17, 32),
             ("completions", 1000, 6)]
    if long_prompt:  # above --seq-parallel-threshold: rides the SP lane
        plan.append(("generate", 900, 8))
    results = [ask(port, k, n, w, i) for i, (k, n, w) in enumerate(plan)]
    slots: list = [None] * len(burst)

    def one(i, k, n, w):
        slots[i] = ask(port, k, n, w, 100 + i)
    threads = [threading.Thread(target=one, args=(i, *b))
               for i, b in enumerate(burst)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    results += [r or (False, "burst request never returned") for r in slots]
    return results


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {series: value}; raises on a malformed line."""
    out = {}
    for ln in text.splitlines():
        if not ln.strip() or ln.startswith("#"):
            continue
        m = re.fullmatch(r"([A-Za-z_:][\w:]*(?:\{.*\})?)\s+(\S+)", ln.strip())
        if not m:
            raise ValueError(f"unparseable /metrics line: {ln!r}")
        out[m.group(1)] = float(m.group(2))
    return out


# -- phases ----------------------------------------------------------------

def check_kernels(kernels: dict, must_hold, dense_ok: bool, where: str):
    calls = kernels.get("calls", {})
    if kernels.get("mode") != "compiled":
        raise Failed(f"{where}: kernels are {kernels.get('mode')!r}, "
                     "not compiled")
    bad = [c for c in calls if c.endswith(":interpret")]
    if bad:
        raise Failed(f"{where}: kernels ran in interpret mode: {bad}")
    if "dense_fallback" in calls and not dense_ok:
        raise Failed(f"{where}: {calls['dense_fallback']} call site(s) "
                     "wanted a kernel and took the dense path")
    for k in must_hold:
        if f"{k}:compiled" not in calls:
            raise Failed(f"{where}: no program holds the {k} kernel "
                         f"(traced: {sorted(calls)})")


def serve_phase(name: str, logs: Path, want_devices: int) -> None:
    flags, must_hold, dense_ok = SERVE_PHASES[name]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    log = logs / f"{name}.log"
    cmd = [sys.executable, "-m", "butterfly_tpu.serve.cli", "serve",
           *SERVE, *flags, "--port", str(port)]
    say(f"{name}: {' '.join(cmd[1:])}")
    t_spawn = time.monotonic()
    proc = spawn(cmd, log)
    try:
        t_warm = t_ready = None
        while t_ready is None:
            if proc.poll() is not None:
                raise Failed(f"{name}: server exited with code "
                             f"{proc.returncode} before listening\n"
                             + tail(log))
            if left() < 60:
                raise Failed(f"{name}: not listening in time\n" + tail(log))
            text = log.read_text(errors="replace")
            if t_warm is None and "warming serving programs" in text:
                t_warm = time.monotonic()
            if "[butterfly] serving " in text:
                t_ready = time.monotonic()
            time.sleep(0.5)
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("[butterfly] serving "))
        say(f"{name}: {line}")
        say(f"{name}: set-up {t_ready - t_spawn:.1f}s = start + weights + "
            f"pool {(t_warm or t_ready) - t_spawn:.1f}s, compile + warm-up "
            f"{t_ready - (t_warm or t_ready):.1f}s")
        t_req = time.monotonic()
        results = send_requests(port, long_prompt=name == "sp4")
        failed = [d for ok, d in results if not ok]
        say(f"{name}: requests sent {len(results)}, succeeded "
            f"{len(results) - len(failed)}, failed {len(failed)}, in "
            f"{time.monotonic() - t_req:.1f}s")
        for ok, detail in results:
            say(f"{name}:   {'ok  ' if ok else 'FAIL'} {detail}")
        try:
            _, raw = http(port, "/health", timeout=30)
        except urllib.error.HTTPError as e:  # 503 when wedged
            raise Failed(f"{name}: /health {e.code}: "
                         f"{e.read().decode('utf-8', 'replace')}")
        health = json.loads(raw)
        if health.get("status") != "ok":
            raise Failed(f"{name}: /health says {health}")
        dev = health["device"]
        say(f"{name}: /health ok; device {dev['platform']} "
            f"{dev['kind']!r} x{dev['count']}; kernels "
            f"{health['kernels']}; allocator {health['allocator']}")
        for i, m in enumerate(dev.get("memory", [])):
            if m:  # the backend reports memory_stats()
                say(f"{name}:   device {i}: " + ", ".join(
                    f"{k} {v}" for k, v in m.items()))
        metrics = parse_metrics(http(port, "/metrics", timeout=30)[1])
        say(f"{name}: /metrics parsed, {len(metrics)} series")
        if failed:
            raise Failed(f"{name}: {len(failed)} request(s) failed")
        if dev["platform"] != PLATFORM or dev["count"] < want_devices:
            raise Failed(f"{name}: server ran on {dev}")
        check_kernels(health["kernels"], must_hold, dense_ok, name)
        if want_devices > 1:
            used = [m.get("bytes_in_use", 0) for m in dev["memory"]]
            if len(used) < want_devices or min(used) < 0.5 * max(used):
                raise Failed(f"{name}: devices do not hold equal shares: "
                             f"bytes_in_use {used}")
        if name == "sp4":
            sp = sum(v for k, v in metrics.items()
                     if "seq_parallel_prefill_tokens_total" in k)
            if sp <= 0:
                raise Failed(f"{name}: no prompt token rode the "
                             "seq-parallel lane")
            say(f"{name}: {int(sp)} prompt tokens rode the SP lane")
    finally:
        rc = stop(proc)
    if rc != 0:
        raise Failed(f"{name}: server exit code {rc} after SIGTERM "
                     "(1 = serving was wedged)\n" + tail(log))
    say(f"{name}: server stopped, exit code 0")


def generate_phase(logs: Path) -> None:
    log = logs / "generate.log"
    cmd = [sys.executable, "-m", "butterfly_tpu.serve.cli", "generate",
           "--model", MODEL, *INT8, "--max-seq", "2048",
           "--max-new", "32", "--prompt", "The quick brown fox"]
    say(f"generate: {' '.join(cmd[1:])}")
    t0 = time.monotonic()
    rc = run(cmd, log, timeout=900)
    text = log.read_text(errors="replace")
    if rc != 0:
        raise Failed(f"generate: exit code {rc}\n" + tail(log))
    m = re.search(r"\[butterfly\] (\d+) tokens in", text)
    d = re.search(r"\[butterfly\] platform=(\S+) device_kind='([^']*)' "
                  r"devices=(\d+) kernels=(\S+) kernel_calls=(\{.*\})", text)
    if not m or not d:
        raise Failed("generate: did not report its tokens and device\n"
                     + tail(log))
    say(f"generate: {m.group(1)} tokens, platform={d.group(1)} "
        f"kernels={d.group(4)} kernel_calls={d.group(5)}, whole run "
        f"{time.monotonic() - t0:.1f}s (set-up included)")
    if int(m.group(1)) != 32:
        raise Failed(f"generate: {m.group(1)} tokens, asked for 32")
    if d.group(1) != PLATFORM:
        raise Failed(f"generate: ran on platform {d.group(1)!r}")
    check_kernels({"mode": d.group(4), "calls": json.loads(d.group(5))},
                  ["flash"], False, "generate")
    # the newest family's toy through the same contiguous engine (gated
    # attention under sandwich norms, sliding layers that rotate beside
    # full ones that do not, a leading dense layer before sigmoid-routed
    # experts: core/config.py tiny("trinity")): a forward the TPU's
    # compiler has to take as the CPU's does
    log = logs / "generate_trinity.log"
    cmd = [sys.executable, "-m", "butterfly_tpu.serve.cli", "generate",
           "--model", "tiny-trinity", "--max-seq", "128", "--max-new", "32",
           "--prompt", "The quick brown fox"]
    say(f"generate: {' '.join(cmd[1:])}")
    rc = run(cmd, log, timeout=600)
    text = log.read_text(errors="replace")
    m = re.search(r"\[butterfly\] (\d+) tokens in", text)
    if rc != 0 or not m or int(m.group(1)) != 32 \
            or f"platform={PLATFORM}" not in text:
        raise Failed(f"generate (tiny-trinity): exit code {rc}, no 32 "
                     f"tokens on {PLATFORM}\n" + tail(log))
    say("generate: tiny-trinity, 32 tokens")


def kernels_phase(logs: Path) -> None:
    log = logs / "kernels.log"
    cmd = [sys.executable, "tools/chip_kernels.py",
           "--out", str(logs / "kernels.json")]
    say(f"kernels: {' '.join(cmd[1:])}")
    rc = run(cmd, log, timeout=600)
    try:
        out = json.loads(log.read_text(errors="replace").splitlines()[-1])
    except (ValueError, IndexError):
        raise Failed(f"kernels: no result (exit code {rc})\n" + tail(log))
    for r in out["results"]:
        say(f"kernels:   {'ok  ' if r['ok'] else 'FAIL'} {r['name']} "
            f"err={r.get('max_err')} hlo={r.get('hlo_has')}"
            + (f" {r['error'][:300]}" if "error" in r else ""))
    if rc != 0 or not out["ok"]:
        raise Failed(f"kernels: failed {out['failed']} "
                     f"(kernels {out['kernels']}, exit code {rc})")
    say(f"kernels: {out['passed']} checks passed: every variant compiled "
        "by Mosaic and agrees with its jnp reference")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default="",
                    help="comma list to run a subset "
                         f"({','.join(ONE_CHIP + FOUR_CHIPS)}); default: "
                         "the one-chip phases, plus the four-chip phases "
                         "where JAX shows four devices")
    args = ap.parse_args()
    missing = [p for p in ("butterfly_tpu/serve/cli.py",
                           "tools/chip_kernels.py")
               if not (HERE / p).exists()]
    if missing:
        print(f"chip_smoke: {missing} not found beside chip_smoke.py — it "
              "drives the repository's own entry points and must run from "
              "the root of a checkout", file=sys.stderr)
        return 2
    logs = HERE / "chiprun_out" / "chip_smoke"
    logs.mkdir(parents=True, exist_ok=True)
    failures = []
    dev = None
    try:
        rc = run([sys.executable, "-c", PROBE], logs / "probe.log", 300)
        try:
            dev = json.loads((logs / "probe.log").read_text()
                             .splitlines()[-1])
        except (ValueError, IndexError):
            dev = None
        if rc != 0 or dev is None:
            print("chip_smoke: JAX did not start\n"
                  + tail(logs / "probe.log"), file=sys.stderr)
            return 2
        if dev["platform"] != PLATFORM:
            print(f"chip_smoke: JAX found no TPU (platform "
                  f"{dev['platform']!r}, device_kind {dev['kind']!r}, "
                  f"{dev['count']} device(s)). This check drives an 8B "
                  "model through the Mosaic kernels and needs the chip; "
                  "nothing was run.", file=sys.stderr)
            return 2
        say(f"device: platform={dev['platform']} device_kind="
            f"{dev['kind']!r} count={dev['count']}; jax {dev['jax']}, "
            f"jaxlib {dev['jaxlib']}, libtpu {dev['libtpu']}")
        phases = [p for p in args.phases.split(",") if p] or (
            ONE_CHIP + (FOUR_CHIPS if dev["count"] >= 4 else []))
        global _budget
        _budget += BUDGET_PER_MESH_PHASE_S * len(
            [p for p in phases if p in FOUR_CHIPS])
        for name in phases:
            try:
                if name in SERVE_PHASES:
                    serve_phase(name, logs, 4 if name in FOUR_CHIPS else 1)
                elif name == "generate":
                    generate_phase(logs)
                elif name == "kernels":
                    kernels_phase(logs)
                else:
                    raise Failed(f"unknown phase {name!r}")
                say(f"{name}: PASSED")
            except Failed as e:
                failures.append(name)
                say(f"{name}: FAILED — {e}")
    finally:
        for p in _children:
            if p.poll() is None:
                stop(p, signal.SIGKILL, wait=10)
    ok = not failures
    out = {"ok": ok, "device": {"platform": dev["platform"],
                                "kind": dev["kind"], "count": dev["count"]}}
    if failures:
        out["failed"] = failures
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
