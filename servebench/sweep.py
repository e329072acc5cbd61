#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one server, several rates.

    python3 servebench/sweep.py --workload mistral7b.chat --rates 0.2,0.3,0.4,0.5,0.65 --seconds 60

Run by hand on the chip when a cell's rate has to be (re)found; the
benchmark's own runs never search for a rate. Each rate gets the cell's
traffic at that rate for `--seconds` after its lead-in, then the server
drains. Prints a table and, as the last line, a JSON object with the
points and the knee by servebench/knee.py. The cell's rate is four
fifths of the knee, written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from servebench import metrics as M                      # noqa: E402
from servebench.client import LoadGenerator              # noqa: E402
from servebench.knee import find_knee, sustained         # noqa: E402
from servebench.manifest import Cell, load_manifest      # noqa: E402
from servebench.server import Server                     # noqa: E402
from servebench.traffic import load_traffic, make_plan   # noqa: E402


def finite(v):
    return None if v is None or math.isinf(v) else v


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    cell = Cell(load_manifest(ROOT), args.workload, ROOT)
    traffic = load_traffic(cell.traffic_path)
    env = dict(os.environ)
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    out = ROOT / "chiprun_out" / "servebench" / f"sweep-{cell.name}"
    out.mkdir(parents=True, exist_ok=True)
    server = Server(cell.config_path, out / "server.log", env,
                    require_tpu=0 if args.rehearsal else cell.chips)
    points = []
    try:
        server.start()
        print(server.wait_ready(1100), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            plan = make_plan(dict(traffic, rate_rps=rate), args.seed + i,
                             args.seconds, cell.config["vocab_size"],
                             cell.config["serve"]["max_seq"])
            gen = LoadGenerator("127.0.0.1", server.port, plan)
            gen.start()
            w0 = gen.t_zero + plan.lead_s
            w1 = w0 + args.seconds
            gen.run(until=w1)
            depth = server.get("/health")["queue_depth"]
            # drain: every request sent gets its answer (or two minutes)
            gen.run(until=w1 + 120, stop_sending_at=w1,
                    done=lambda: not gen.live)
            st = gen.streams
            gen.stop()
            mid = (w0 + w1) / 2
            tt = M.ttfts(st, w0, w1)
            p = {"rate_rps": rate, "sent": len(st),
                 "failed": sum(1 for s in st if s.failed),
                 "due_in_window": len(tt),
                 "out_tok_s": M.out_tok_s(st, w0, w1)[0],
                 "ttft_p50_ms": finite(M.ttft_p50_ms(st, w0, w1)[0]),
                 "ttft_p95_ms": finite(M.percentile(tt, 95) * 1e3) if tt else None,
                 "ttft_p50_first_half_ms": finite(M.ttft_p50_ms(st, w0, mid)[0]),
                 "ttft_p50_second_half_ms": finite(M.ttft_p50_ms(st, mid, w1)[0]),
                 "tpot_p50_ms": M.tpot_p50_ms(st, w0, w1)[0],
                 "gap_p95_ms": M.gap_pct_ms(95)(st, w0, w1)[0],
                 "queue_depth_close": depth,
                 "drain_s": time.monotonic() - w1}
            p["sustained"] = sustained(p)
            points.append(p)
            print(json.dumps(p), flush=True)
    finally:
        rc = server.stop()
    print(json.dumps({"points": points, "knee": find_knee(points),
                      "server_rc": rc}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
