"""BTF003 negative fixture: the same sync primitives OUTSIDE the hot
set (the drain is where synchronization belongs), and the blessed
host->host operand assembly inside a hot function. Expected findings: 0.
"""
from typing import List

import numpy as np


class Sched:
    def tick(self):
        # operand assembly from host lists is host->host, not a sync
        temps = np.asarray([r.temperature for r in self.running])
        active = np.zeros((8,), bool)
        return self._mixed_block(4), temps, active

    def _mixed_block(self, k: int):
        budgets = np.maximum(self._base - k, 0)   # numpy math, no fetch
        return budgets

    def sp_prefill_chunk(self, slot: int, tokens: List[int]):
        # annotated host-container params: asarray over them is assembly
        buf = np.asarray(tokens, np.int32)
        return buf

    def _drain_blocks(self, blocks):
        # the drain is the one blessed fetch point (not a hot function)
        vals = np.asarray(self._pending)
        return vals.tolist(), int(vals[0])


class TickLog:
    def record(self, wall_s, phases):
        # the blessed tick-anatomy pattern: host floats + dict copies
        # under a tiny lock — no device value anywhere near the ring
        entry = {"wall_s": wall_s, "phases": dict(phases)}
        with self._lock:
            self._ring.append(entry)


class FlightRecorder:
    def note(self, kind, **attrs):
        ev = {"kind": kind}
        ev.update(attrs)
        self._ring.append(ev)

    def poll(self, signals):
        # trigger predicates over a HOST dict snapshot: plain compares
        burn = signals.get("slo_burn_rate", 0.0)
        return burn >= self.threshold


class SignalRecorder:
    def sample(self, gauges, rates=None, t_wall=0.0):
        # the blessed time-series pattern: caller hands in host floats
        # (registry snapshot + len()s), the ring sees no device values
        signals = dict(gauges)
        for name, cum in (rates or {}).items():
            signals[name] = max(0.0, cum - self._prev.get(name, 0.0))
        self._ring.append({"t_wall": t_wall, "signals": signals})


def evaluate_rules(rules, samples):
    # predicates over host sample dicts: plain float compares
    return [r for r in rules
            if samples and samples[-1]["signals"].get(r.signal, 0.0)
            > r.threshold]
