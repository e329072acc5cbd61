#!/usr/bin/env python
"""Render a per-request timeline report from a dumped trace.

Input: the JSON a running server returns from ``GET /debug/requests``
(or ``Tracer.dump_json``). Output: a per-request summary table (queue
wait, prefill, TTFT, decode, totals) and, with ``--timeline ID``, the
full event list for one request with inter-event deltas — the "where
did this request's time go" view.

    curl -s localhost:8000/debug/requests > trace.json
    python tools/trace_report.py trace.json
    python tools/trace_report.py trace.json --timeline 17

``--ticks FILE`` (a ``GET /debug/ticks`` body) adds the count of
scheduler ticks and of the tokens they generated to the summary, the
full drain barriers by cause beside the finishes taken at a lazy drain
without one, the starvation clock's reading of those ticks: the seconds the device
waited for the host before their launches, by cause and by span; and
the CPU clock's: the tick thread's CPU seconds, where it was off a CPU
by span, and every stalled tick's account (tools/tick_report.py's lines).

``--fleet`` renders a MERGED cross-replica trace instead — the JSON a
fleet control plane returns from ``GET /fleet/trace?request_id=``: the
control-plane leg waterfall (classify → prefill_leg → kv transfer →
decode_leg), every involved replica's span events interleaved on the
control plane's clock, per-leg durations, and the SLO verdicts.

    curl -s "localhost:8100/fleet/trace?request_id=abc" > fleet.json
    python tools/trace_report.py --fleet fleet.json

stdlib-only on purpose: runs anywhere the dump lands (laptop, CI), no
jax / no backend required.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional


def _summarize_timeline():
    """Resolve obs.trace.summarize_timeline WITHOUT importing the
    butterfly_tpu package root (which drags in jax): the trace module is
    stdlib-only, so a checkout loads it straight from its file. Falls
    back to the package import for installed layouts."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "butterfly_tpu", "obs", "trace.py")
    if os.path.exists(path):
        import importlib.util
        spec = importlib.util.spec_from_file_location("_bt_obs_trace", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.summarize_timeline
    from butterfly_tpu.obs.trace import summarize_timeline
    return summarize_timeline


def _fmt_s(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v >= 1.0:
        return f"{v:.2f}s"
    return f"{v * 1e3:.1f}ms"


def _fmt(v: Any) -> str:
    return "-" if v is None else str(v)


def load_dump(path: str) -> Dict[str, Any]:
    with open(path) as f:
        dump = json.load(f)
    if not isinstance(dump, dict) or "requests" not in dump:
        raise ValueError(
            f"{path}: not a trace dump (expected a JSON object with a "
            f"'requests' key — the GET /debug/requests body)")
    return dump


def summary_rows(dump: Dict[str, Any]) -> List[Dict[str, Any]]:
    summarize = _summarize_timeline()
    return [summarize(rec) for rec in dump.get("requests", ())]


def render_summary(dump: Dict[str, Any],
                   ticks: Optional[Dict[str, Any]] = None) -> str:
    rows = summary_rows(dump)
    cols = [("id", 5), ("request_id", 14), ("state", 9), ("queue", 8),
            ("prefill", 8), ("ttft", 8), ("decode", 8), ("total", 8),
            ("toks", 5), ("chunks", 6), ("preempt", 7)]
    out = [" ".join(f"{name:>{w}}" for name, w in cols)]
    for r in rows:
        vals = [_fmt(r["id"]), _fmt(r["request_id"])[:14], _fmt(r["state"]),
                _fmt_s(r["queue_wait_s"]), _fmt_s(r["prefill_s"]),
                _fmt_s(r["ttft_s"]), _fmt_s(r["decode_s"]),
                _fmt_s(r["total_s"]), _fmt(r["tokens"]),
                _fmt(r["prefill_chunks"]), _fmt(r["preemptions"])]
        out.append(" ".join(f"{v:>{w}}" for v, (_, w) in zip(vals, cols)))
    done = [r for r in rows if r["total_s"] is not None]
    out.append("")
    out.append(f"{len(rows)} request(s), {len(done)} with a complete "
               f"submit->finish timeline")
    if done:
        ttfts = sorted(r["ttft_s"] for r in done
                       if r["ttft_s"] is not None)
        if ttfts:
            out.append(
                f"ttft: min {_fmt_s(ttfts[0])}  "
                f"p50 {_fmt_s(ttfts[len(ttfts) // 2])}  "
                f"max {_fmt_s(ttfts[-1])}")
    n_glob = len(dump.get("global_events", ()))
    if n_glob:
        out.append(f"{n_glob} global event(s)")
    if ticks is not None:
        recs = ticks.get("ticks", ())
        out.append(f"{len(recs)} tick(s), "
                   f"{sum(t.get('generated', 0) for t in recs)} token(s) "
                   "generated")
        out.extend(barrier_lines(recs))
        out.extend(starved_lines(recs))
        out.extend(cpu_clock_lines(recs))
    return "\n".join(out)


def barrier_lines(recs) -> List[str]:
    """The full drain barriers of a /debug/ticks dump by cause, most
    first, beside the finishes a lazy drain took without one. Nothing
    for the records of a program that took no finish so."""
    recs = [t for t in recs if "finishes_inline" in t]
    if not recs:
        return []
    causes: Dict[str, int] = {}
    for t in recs:
        for c in t["barrier_causes"]:
            causes[c] = causes.get(c, 0) + 1
    return ["full barriers: " + ("  ".join(
        f"{c} {n}" for c, n in sorted(causes.items(),
                                      key=lambda kv: -kv[1])) or "none")
        + "; finishes at a lazy drain, no barrier: "
        f"{sum(t['finishes_inline'] for t in recs)}"]


def starved_lines(recs) -> List[str]:
    """The starvation clock of a /debug/ticks dump: how long the device
    waited for the host before the ticks' launches, as a share of the
    time the ticks span, then by cause and by span, most first. Nothing
    for the records of a program older than the clock."""
    recs = [t for t in recs if "starved_s" in t]
    if not recs:
        return []
    total = sum(t["starved_s"] or 0.0 for t in recs)
    span = sum(t["wall_s"] + t["gap_s"] for t in recs)
    causes: Dict[str, float] = {}
    spans: Dict[str, float] = {}
    for t in recs:
        if t["starved_s"]:
            c = t["starved_cause"]
            causes[c] = causes.get(c, 0.0) + t["starved_s"]
        for name, s in t["starved_by"].items():
            spans[name] = spans.get(name, 0.0) + s
    out = [f"device starved {_fmt_s(total)} of {_fmt_s(span)}"
           + (f" ({100.0 * total / span:.1f}%)" if span > 0 else "")
           + f" in {sum(1 for t in recs if t['starved_s'])} of "
           f"{len(recs)} tick(s), "
           f"{sum(1 for t in recs if t['profiled'])} under a capture"]
    for label, table in (("by cause", causes), ("by span", spans)):
        if table:
            out.append(f"  {label}: " + "  ".join(
                f"{k} {_fmt_s(v)}" for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])))
    return out


def cpu_clock_lines(recs) -> List[str]:
    """The CPU clock of a /debug/ticks dump, as tools/tick_report.py
    prints it (the file beside this one; stdlib only): nothing for the
    records of a program older than the clock."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tick_report", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tick_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return [ln for ln in mod.cpu_lines(mod.cpu_stats(list(recs))) if ln]


def render_timeline(dump: Dict[str, Any], rid: int) -> str:
    rec = next((r for r in dump.get("requests", ())
                if r.get("id") == rid), None)
    if rec is None:
        raise ValueError(f"no request with id {rid} in the dump "
                         f"(have: {[r.get('id') for r in dump['requests']]})")
    events = rec.get("events", [])
    out = [f"request {rid}"
           + (f" (request_id={rec['request_id']})"
              if rec.get("request_id") else "")]
    t0 = events[0]["t"] if events else 0.0
    prev = t0
    for ev in events:
        t = ev["t"]
        attrs = " ".join(f"{k}={v}" for k, v in ev.items()
                         if k not in ("t", "name"))
        out.append(f"  +{t - t0:9.4f}s (Δ{_fmt_s(t - prev)}) "
                   f"{ev['name']:<14} {attrs}")
        prev = t
    return "\n".join(out)


def load_fleet_dump(path: str) -> Dict[str, Any]:
    with open(path) as f:
        dump = json.load(f)
    if not isinstance(dump, dict) or "merged" not in dump:
        raise ValueError(
            f"{path}: not a merged fleet trace (expected a JSON object "
            f"with a 'merged' key — the GET /fleet/trace?request_id= "
            f"body)")
    return dump


def render_fleet(dump: Dict[str, Any]) -> str:
    """The cross-replica waterfall: control-plane legs with durations,
    then every source's events interleaved on the common clock."""
    out = [f"fleet trace request_id={dump.get('request_id')}"]
    t0 = dump.get("t0_wall") or 0.0
    legs = dump.get("legs", [])
    if legs:
        out.append("legs (control plane):")
        for leg in legs:
            where = leg.get("replica") or "-"
            status = leg.get("status", "")
            out.append(f"  +{leg['start_wall'] - t0:9.4f}s "
                       f"{leg['name']:<12} {_fmt_s(leg['dur_s']):>9}  "
                       f"{where}{('  [' + status + ']') if status and status != 'ok' else ''}")
        total, legsum = dump.get("total_s"), dump.get("legs_total_s")
        if total:
            out.append(f"  legs sum {_fmt_s(legsum)} of "
                       f"{_fmt_s(total)} end-to-end "
                       f"({legsum / total * 100:.1f}% accounted)")
    out.append("merged timeline:")
    width = max((len(ev.get("source", "")) for ev in dump["merged"]),
                default=7)
    prev = t0
    for ev in dump["merged"]:
        t = ev["t_wall"]
        attrs = " ".join(f"{k}={v}" for k, v in ev.items()
                         if k not in ("t", "t_wall", "name", "source",
                                      "replica_req"))
        out.append(f"  +{t - t0:9.4f}s (Δ{_fmt_s(max(0.0, t - prev)):>7}) "
                   f"[{ev.get('source', ''):<{width}}] "
                   f"{ev['name']:<14} {attrs}")
        prev = t
    srcs = dump.get("sources", {})
    if srcs:
        parts = []
        for name, info in srcs.items():
            if info.get("missing"):
                parts.append(f"{name}: MISSING ({info.get('error', '?')})")
            else:
                off = info.get("offset_s")
                parts.append(f"{name}: {info.get('events', 0)} event(s)"
                             + (f", clock offset {off * 1e3:+.1f}ms"
                                if off else ""))
        out.append("sources: " + "; ".join(parts))
    slo = dump.get("slo")
    if slo:
        verdicts = []
        if "slo_ttft_ok" in slo:
            verdicts.append(
                f"ttft {_fmt_s(slo.get('ttft_s'))} -> "
                f"{'OK' if slo['slo_ttft_ok'] else 'VIOLATED'}")
        if "slo_itl_ok" in slo:
            verdicts.append(
                f"itl_mean {_fmt_s(slo.get('itl_mean_s'))} -> "
                f"{'OK' if slo['slo_itl_ok'] else 'VIOLATED'}")
        out.append("slo: " + ("; ".join(verdicts) if verdicts
                              else "no objectives declared"))
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="trace_report",
        description="summarize a /debug/requests trace dump (or, with "
                    "--fleet, a merged /fleet/trace dump)")
    p.add_argument("dump", help="path to the JSON trace dump")
    p.add_argument("--timeline", type=int, default=None, metavar="ID",
                   help="print one request's full event timeline")
    p.add_argument("--fleet", action="store_true",
                   help="render a merged cross-replica fleet trace "
                        "(the GET /fleet/trace?request_id= body)")
    p.add_argument("--ticks", default=None, metavar="FILE",
                   help="a GET /debug/ticks body: adds the starved "
                        "seconds by cause and by span, and the tick count "
                        "to the summary")
    p.add_argument("--json", action="store_true",
                   help="emit the per-request summaries as JSON instead "
                        "of a table")
    args = p.parse_args(argv)
    try:
        if args.fleet:
            print(render_fleet(load_fleet_dump(args.dump)))
            return 0
        dump = load_dump(args.dump)
        if args.timeline is not None:
            print(render_timeline(dump, args.timeline))
        elif args.json:
            print(json.dumps(summary_rows(dump)))
        else:
            ticks = None
            if args.ticks:
                with open(args.ticks) as f:
                    ticks = json.load(f)
                if isinstance(ticks, list):   # the benchmark's ticks.json
                    ticks = {"ticks": ticks}
            print(render_summary(dump, ticks))
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
