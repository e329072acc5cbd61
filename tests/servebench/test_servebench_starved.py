"""The readers of the program's starvation clock (servebench/starved.py
and its six per-layer entries, PR 38) on tick records written by hand,
where every number is known; their entries in the manifest; and the
toy's traced rehearsal on the CPU, whose line carries all six because the
real scheduler keeps the clock whatever it runs on.
"""
import hashlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import starved  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from test_servebench_run import checkout, last_json, run  # noqa: E402,F401

MANIFEST = load_manifest(ROOT)
SIX = {
    "starved_share": "device",
    "starved_finish_share": "scheduler (sched/scheduler.py)",
    "starved_flush_count_share": "scheduler (sched/scheduler.py)",
    "starved_emit_share": "scheduler (sched/scheduler.py)",
    "starved_admit_share": "scheduler (sched/scheduler.py)",
    "starved_launch_share": "engine programs (engine/serving.py)",
}
PARTS = [n for n in SIX if n != "starved_share"]
#: the 22 entries the benchmark had before PR 38, in their order
EARLIER = ["tick_host_share", "slot_occupancy", "kv_pages_used_peak",
           "preemptions", "prefill_tok_s", "block_roofline",
           "paged_attn_share", "device_idle_share", "collective_share",
           "mixed_block_ms_p50", "mixed_tick_share", "drain_overlap_share",
           "idle_host_share", "idle_fetch_share", "idle_outside_tick_share",
           "compiles_in_window", "experts_touched_share", "expert_rows_skew",
           "flush_ms_p50", "kv_selected_share", "sparse_attn_share",
           "sparse_attn_roofline"]
FIELDS = ("name", "unit", "better", "source", "layer", "moves")


def reader(name):
    return Cell(MANIFEST, "mistral7b.batch").reader(name)


def tick(seq, t_wall, wall_s=0.09, gap_s=0.01, starved_s=0.0, cause=None,
         by=None, profiled=False):
    return dict(seq=seq, t_wall=t_wall, wall_s=wall_s, gap_s=gap_s,
                starved_s=starved_s, starved_cause=cause,
                starved_by=dict(by or {}), profiled=profiled)


def ctx_of(ticks):
    # the window is [1100, 1145) on the records' wall clock
    return SimpleNamespace(ticks=ticks, w0=100.0, w1=145.0,
                           wall_minus_mono=1000.0, info={})


# eleven ticks of 0.1 s inside the window, two of them under a capture, one
# before the window and one polled twice:
#   finish    0.030 = flush_count 0.012 + emit 0.006 + admit 0.002
#                     + admit.seed 0.004 + mixed 0.001 + dispatch.put 0.003
#                     + dispatch.launch 0.001 + other 0.001
#   exposed   0.010 = emit 0.004 + assemble 0.002 + outside_tick 0.004
#   late_tick 0.005 = spec_emit 0.001 + dispatch 0.004
#   a tick that launched nothing (null), five that launched onto a busy
#   device; under the capture: finish 0.080, and a busy one
FINISH = {"drain.flush_count": 0.012, "drain.emit": 0.006, "admit": 0.002,
          "admit.seed": 0.004, "mixed": 0.001, "dispatch.put": 0.003,
          "dispatch.launch": 0.001, "other": 0.001}
HAND = (
    [tick(1, 1090.0, starved_s=0.5, cause="finish", by={"admit": 0.5})]
    + [tick(2, 1101.0, starved_s=0.030, cause="finish", by=FINISH)] * 2
    + [tick(3, 1102.0, starved_s=0.010, cause="exposed",
            by={"drain.emit": 0.004, "assemble": 0.002,
                "outside_tick": 0.004}),
       tick(4, 1103.0, starved_s=0.005, cause="late_tick",
            by={"spec_emit": 0.001, "dispatch": 0.004}),
       tick(5, 1104.0, starved_s=None)]
    + [tick(6 + i, 1105.0 + i) for i in range(5)]
    + [tick(11, 1142.0, starved_s=0.080, cause="finish",
            by={"drain.flush_count": 0.050, "admit.seed": 0.030},
            profiled=True),
       tick(12, 1143.0, profiled=True)])


def test_the_six_readers_on_records_written_by_hand():
    ctx = ctx_of(HAND)
    got = {name: reader(name)(ctx) for name in SIX}
    # nine untraced ticks of 0.09 + 0.01 s span 0.9 s
    assert got["starved_share"] == pytest.approx(100 * 0.045 / 0.9)
    assert got["starved_finish_share"] == pytest.approx(100 * 0.030 / 0.9)
    assert got["starved_flush_count_share"] == pytest.approx(100 * 0.012 / 0.9)
    assert got["starved_emit_share"] == pytest.approx(100 * 0.011 / 0.9)
    assert got["starved_admit_share"] == pytest.approx(100 * 0.006 / 0.9)
    assert got["starved_launch_share"] == pytest.approx(100 * 0.011 / 0.9)
    # the parts by span never pass the whole, nor does the part by cause
    by_span = [n for n in PARTS if n != "starved_finish_share"]
    assert sum(got[n] for n in by_span) <= got["starved_share"] + 1e-9
    assert got["starved_finish_share"] <= got["starved_share"]
    # what the first reader leaves for the info line
    info = ctx.info
    assert info["starved_ticks"] == 9
    assert info["starved_share_profiled"] == pytest.approx(100 * 0.080 / 0.2)
    assert [c for c, _ in info["starved_by_cause"]] == [
        "finish", "exposed", "late_tick"]
    assert dict(info["starved_by_cause"]) == pytest.approx(
        {"finish": 0.030, "exposed": 0.010, "late_tick": 0.005})
    spans = dict(info["starved_by_span"])
    assert spans == pytest.approx({
        "drain.flush_count": 0.012, "drain.emit": 0.010, "admit": 0.002,
        "admit.seed": 0.004, "mixed": 0.001, "dispatch.put": 0.003,
        "dispatch.launch": 0.001, "other": 0.001, "assemble": 0.002,
        "outside_tick": 0.004, "spec_emit": 0.001, "dispatch": 0.004})
    assert sum(spans.values()) == pytest.approx(0.045)
    assert info["starved_by_span"][0][0] == "drain.flush_count"
    json.dumps(info)


@pytest.mark.parametrize("name", list(SIX))
def test_a_reader_gives_none_on_an_older_program_and_zero_on_a_fed_device(name):
    """The parent's tick records carry no `starved_s`: None, and nothing
    in the info line. Records that carry it and starved nothing: 0.0."""
    old = [{k: v for k, v in t.items()
            if k in ("seq", "t_wall", "wall_s")} for t in HAND]
    ctx = ctx_of(old)
    assert reader(name)(ctx) is None and ctx.info == {}
    assert reader(name)(ctx_of([])) is None
    fed = ctx_of([tick(2 + i, 1101.0 + i) for i in range(6)]
                 + [tick(9, 1110.0, starved_s=None)])
    assert reader(name)(fed) == 0.0
    if name == "starved_share":
        assert fed.info["starved_by_span"] == [] == fed.info["starved_by_cause"]
        assert fed.info["starved_ticks"] == 7
        assert fed.info["starved_share_profiled"] is None
    # a window whose every tick ran under a capture has no untraced figure
    captured = ctx_of([tick(2, 1101.0, starved_s=0.01, cause="finish",
                            by={"admit": 0.01}, profiled=True)])
    assert reader(name)(captured) is None
    assert starved.share(captured, starved.whole, profiled=True) \
        == pytest.approx(10.0)


def test_the_six_entries_are_appended_and_the_earlier_ones_unchanged():
    per_layer = MANIFEST["per_layer"]
    assert [m["name"] for m in per_layer[:22]] == EARLIER
    rows = [[m[k] for k in FIELDS] for m in per_layer[:22]]
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() == (
        "5fd767e07a7eb787093a439b2b9d6bfab2d69c708a184c8b24d5ba880cbe1279")
    assert per_layer[22:28] == [
        {"name": name, "unit": "%", "better": "lower",
         "source": "program_span", "layer": layer, "moves": "tpot_p50_ms"}
        for name, layer in SIX.items()]
    # no list of cells: every cell reports them, the new ones too
    for cell in MANIFEST["workloads"]:
        assert set(SIX) <= {m["name"] for m in
                            Cell(MANIFEST, cell["name"]).per_layer}
    for name in SIX:
        assert callable(reader(name))


def test_the_traced_rehearsal_carries_all_six(checkout):  # noqa: F811
    """The toy runs the real scheduler on the CPU, so its traced line
    holds the six readings and its info line the tables."""
    r = run(checkout, "--workload", "tiny.batch", "--seed", "38",
            "--seconds", "4", "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = last_json(r)
    assert out["correct"] is True, r.stderr[-3000:]
    m = out["metrics"]
    assert set(SIX) <= set(m)
    for name in SIX:
        assert m[name]["unit"] == "%" and 0.0 <= m[name]["value"] <= 100.0
    whole = m["starved_share"]["value"]
    assert m["starved_finish_share"]["value"] <= whole + 1e-9
    assert sum(m[n]["value"] for n in PARTS
               if n != "starved_finish_share") <= whole + 1e-9
    assert info["starved_ticks"] > 0
    # the rehearsal's capture runs on the CPU too: its ticks are left out
    # of the six and counted apart
    assert 0.0 <= info["starved_share_profiled"] <= 100.0
    assert sum(s for _, s in info["starved_by_cause"]) == pytest.approx(
        sum(s for _, s in info["starved_by_span"]), abs=1e-6)
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    assert {t["profiled"] for t in ticks} == {False, True}
    for t in ticks:
        assert t["gap_s"] >= 0.0
        if t["program"] is None:
            assert t["starved_s"] is None
        else:
            assert sum(t["starved_by"].values()) == pytest.approx(
                t["starved_s"], abs=1e-6)
            assert (t["starved_cause"] is not None) == (t["starved_s"] > 0)
