"""The rule that picks the chat cell's rate from a sweep of rates.

The arithmetic of `butterfly_tpu/workload/sweep.py:find_knee`, copied so
that no later PR can move the yardstick: the knee is the point of most
throughput among the points whose TTFT tail stays within `slack` times
the grid's best. Here a point is an offered rate, and a point whose queue
was still growing when its window closed is not sustained and cannot be
the knee.

stdlib only.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def sustained(point: Dict, growth: float = 2.0, queue: int = 2) -> bool:
    """No growing queue: TTFT in the window's second half at most
    `growth` times the first half's, and at most `queue` requests
    waiting at the close."""
    a, b = point.get("ttft_p50_first_half_ms"), point.get("ttft_p50_second_half_ms")
    if a is None or b is None or b > growth * a:
        return False
    return point.get("queue_depth_close", 0) <= queue


def find_knee(points: List[Dict], slack: float = 2.0) -> Optional[Dict]:
    usable = [p for p in points
              if p.get("ttft_p95_ms") is not None and sustained(p)]
    if not usable:
        return None
    floor = min(p["ttft_p95_ms"] for p in usable)
    eligible = [p for p in usable if p["ttft_p95_ms"] <= slack * floor] or usable
    best = max(eligible, key=lambda p: p["out_tok_s"])
    return {"rate_rps": best["rate_rps"], "out_tok_s": best["out_tok_s"],
            "ttft_p95_ms": best["ttft_p95_ms"],
            "rule": f"most tokens/s among sustained rates with ttft_p95 <= "
                    f"{slack:g}x the grid's least ({floor:.0f} ms)"}
