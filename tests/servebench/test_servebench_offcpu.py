"""The readers of the tick's CPU clock (servebench/offcpu.py and its three
per-layer entries, PR 54) on tick records written by hand, where every
number is known; their entries in the manifest; and the toy's traced
rehearsal on the CPU, whose line carries all three because the real
scheduler keeps the clock whatever it runs on.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import offcpu  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from test_servebench_run import checkout, last_json, run  # noqa: E402,F401

MANIFEST = load_manifest(ROOT)
THREE = {"tick_cpu_ms_p50": "ms", "tick_off_cpu_share": "%",
         "stall_ticks": "count"}


def reader(name):
    return Cell(MANIFEST, "mistral7b.batch").reader(name)


def tick(seq, t_wall, wall_s=0.1, cpu_s=0.03, off=None, fetch_s=0.06,
         proc_cpu_s=0.05, gc_s=0.0, gc_collections=0, run_delay_s=0.0,
         stall=None, profiled=False, program="bf_mixed_block_win"):
    return dict(seq=seq, t_wall=t_wall, wall_s=wall_s, cpu_s=cpu_s,
                off_cpu_by=None if off is None else dict(off),
                fetch_s=fetch_s,
                proc_cpu_s=proc_cpu_s, gc_s=gc_s,
                gc_collections=gc_collections, gc_generation=None,
                run_delay_s=run_delay_s, stall=stall, profiled=profiled,
                program=program, barrier_causes=[])


def ctx_of(ticks):
    # the window is [1100, 1145) on the records' wall clock
    return SimpleNamespace(ticks=ticks, w0=100.0, w1=145.0,
                           wall_minus_mono=1000.0, info={})


# a tick's 0.1 s: 0.03 of CPU, 0.06 waiting for the device in its fetch,
# 0.01 waiting elsewhere
USUAL = {"drain.fetch": 0.06, "drain.emit": 0.006, "dispatch.launch": 0.003,
         "other": 0.001}
STALL = {"phase": "mixed", "span": "dispatch.launch", "cause": "blocked",
         "excess_s": 3.75}
HAND = (
    # before the window
    [tick(1, 1090.0, cpu_s=0.9, off={"admit": 9.0}, stall=STALL)]
    + [tick(2 + i, 1101.0 + i, off=USUAL) for i in range(6)]
    # two that read the thread's CPU clock at their two ends alone: 0.2 s
    # of wall that no table is taken over, 0.04 s of CPU
    + [tick(20 + i, 1120.0 + i, cpu_s=0.02, proc_cpu_s=None, off=None)
       for i in range(2)]
    # a full barrier: the wait for the flush count is the device's too; a
    # collection of 4 ms, 2 ms runnable without a CPU, and no launch
    + [tick(8, 1108.0, cpu_s=0.02, fetch_s=0.07, gc_s=0.004,
            gc_collections=1, run_delay_s=0.002, program=None,
            off={"drain.fetch": 0.05, "drain.flush_count": 0.02,
                 "admit": 0.01})]
    # the stall, polled twice: 3.85 s of wall, 3.78 of them in the launch
    + [tick(9, 1109.0, wall_s=3.85, cpu_s=0.04, proc_cpu_s=0.10,
            stall=STALL, off={"drain.fetch": 0.03,
                              "dispatch.launch": 3.78})] * 2
    # under the capture: left out of all three
    + [tick(10, 1142.0, wall_s=0.3, cpu_s=0.2, off={"drain.emit": 0.1},
            stall=dict(STALL, cause="on_cpu"), profiled=True)])


def test_the_three_readers_on_records_written_by_hand():
    ctx = ctx_of(HAND)
    got = {name: reader(name)(ctx) for name in THREE}
    # nine ticks launched a block, fewer than a run of 16, so one mean:
    # six of 30 ms of CPU, one of 40, two of 20
    assert got["tick_cpu_ms_p50"] == pytest.approx(260.0 / 9)
    # eight ticks carry the table and span 6 x 0.1 + 0.1 + 3.85 s; outside
    # the device waits they waited 6 x 0.01 + 0.01 + 3.78 s
    wall = 0.7 + 3.85
    assert got["tick_off_cpu_share"] == pytest.approx(100 * 3.85 / wall)
    assert got["stall_ticks"] == 1.0
    info = ctx.info
    assert info["off_cpu_ticks"] == 8
    assert info["off_cpu_wall_s"] == pytest.approx(wall)
    assert info["other_threads_cpu_share"] == pytest.approx(
        100 * (6 * 0.02 + 0.03 + 0.06) / wall)
    wall += 0.2             # the figures every tick takes are over all ten
    assert info["tick_cpu_share"] == pytest.approx(100 * 0.28 / wall)
    spans = dict(info["off_cpu_by_span"])
    assert info["off_cpu_by_span"][0][0] == "dispatch.launch"
    assert spans == pytest.approx({
        "drain.fetch": 0.36 + 0.05 + 0.03, "drain.flush_count": 0.02,
        "drain.emit": 0.036, "dispatch.launch": 0.018 + 3.78,
        "other": 0.006, "admit": 0.01})
    # off the CPU in the two device waits, over the wall clock's fetch_s
    assert info["fetch_off_cpu_agrees"] == pytest.approx(
        (0.44 + 0.02) / (0.36 + 0.07 + 0.06))
    assert info["run_delay_share"] == pytest.approx(100 * 0.002 / wall)
    assert info["gc_share"] == pytest.approx(100 * 0.004 / wall)
    assert info["gc_collections"] == 1
    (acct,) = info["stalled_ticks"]
    assert acct["seq"] == 9 and acct["stall"] == STALL
    assert acct["wall_s"] == 3.85 and acct["cpu_s"] == 0.04
    assert acct["off_cpu_by"]["dispatch.launch"] == 3.78
    assert set(offcpu.ACCOUNT) == set(acct)
    json.dumps(info)


@pytest.mark.parametrize("name", list(THREE))
def test_a_reader_gives_none_on_an_older_program_and_zero_where_nothing_waited(
        name):
    """The parent's tick records carry no `cpu_s`: None, and nothing in
    the info line. Records that carry it and waited for nothing, stalled
    nowhere and worked no time: 0.0."""
    old = [{k: v for k, v in t.items()
            if k in ("seq", "t_wall", "wall_s", "fetch_s", "profiled",
                     "program")} for t in HAND]
    ctx = ctx_of(old)
    assert reader(name)(ctx) is None and ctx.info == {}
    assert reader(name)(ctx_of([])) is None
    # records made by a caller that passes no clock say None too
    unclocked = ctx_of([dict(t, cpu_s=None, off_cpu_by=None) for t in HAND])
    assert reader(name)(unclocked) is None and unclocked.info == {}
    idle = ctx_of([tick(2 + i, 1101.0 + i, cpu_s=0.0, fetch_s=0.0,
                        proc_cpu_s=0.0, off={}) for i in range(6)])
    assert reader(name)(idle) == 0.0
    if name == "tick_off_cpu_share":
        assert idle.info["off_cpu_by_span"] == []
        assert idle.info["fetch_off_cpu_agrees"] is None
        assert idle.info["gc_share"] == 0.0 == idle.info["run_delay_share"]
    if name == "stall_ticks":
        assert idle.info["stalled_ticks"] == []
    # a window whose every tick ran under a capture has no untraced figure
    captured = ctx_of([tick(2, 1101.0, off={"admit": 0.01}, stall=STALL,
                            profiled=True)])
    assert reader(name)(captured) is None


def test_the_median_tick_is_the_median_of_the_means_of_runs_of_sixteen():
    """A CPU clock that moves in steps of 10 ms reads 30 ms of work as 20,
    30 or 40: the median over single ticks would sit on a step. 40 ticks
    launched: a run of 16 that reads 31.25 on average, one that reads
    28.75, and a last half run of 8 that reads 60 (a stall among them);
    one more tick would be too few to count as a run."""
    def run(n, per16):
        return [0.01 * c for c in per16 * (n // len(per16))]
    cpu = (run(16, [3, 3, 4, 3, 3, 2, 4, 3]) + run(16, [3, 2, 4, 3, 3, 2, 3, 3])
           + run(8, [3, 3, 3, 3, 3, 3, 3, 27]))
    ticks = [tick(2 + i, 1101.0 + i * 0.1, cpu_s=c, off=None)
             for i, c in enumerate(cpu)]
    ctx = ctx_of(ticks + [tick(99, 1140.0, cpu_s=9.0, off=None)])
    assert offcpu.GROUP == 16
    assert reader("tick_cpu_ms_p50")(ctx) == pytest.approx(31.25)
    assert offcpu.cpu_ms_p50(ticks[:32]) == pytest.approx(30.0)
    assert offcpu.cpu_ms_p50(ticks[:5]) == pytest.approx(32.0)
    assert offcpu.cpu_ms_p50([]) is None
    # no tick of the window carries the table: the share has nothing to
    # be taken over, the process's figures are there all the same
    assert reader("tick_off_cpu_share")(ctx) is None
    assert ctx.info["off_cpu_ticks"] == 0 and ctx.info["off_cpu_by_span"] == []
    assert ctx.info["tick_cpu_share"] > 0 and ctx.info["gc_share"] == 0.0


def test_the_device_waits_are_left_out_and_a_null_run_delay_says_null():
    waits = ctx_of([tick(2, 1101.0, off={"drain.fetch": 0.05,
                                         "drain.flush_count": 0.02}),
                    tick(3, 1102.0, off={"drain.fetch": 0.06},
                         run_delay_s=None)])
    assert reader("tick_off_cpu_share")(waits) == 0.0
    assert waits.info["fetch_off_cpu_agrees"] == pytest.approx(0.13 / 0.12)
    assert waits.info["run_delay_share"] is None
    # no tick of the window launched: a share still, no median of none
    none = ctx_of([tick(2, 1101.0, off={"admit": 0.02}, program=None)])
    assert reader("tick_cpu_ms_p50")(none) is None
    assert reader("tick_off_cpu_share")(none) == pytest.approx(20.0)
    assert reader("stall_ticks")(none) == 0.0


def test_the_three_entries_are_appended_to_the_manifest():
    per_layer = MANIFEST["per_layer"]
    assert per_layer[-3:] == [
        {"name": name, "unit": unit, "better": "lower",
         "source": "program_span", "layer": "scheduler (sched/scheduler.py)",
         "moves": "tpot_p50_ms"} for name, unit in THREE.items()]
    # no list of cells: every cell reports them, the new ones too
    for cell in MANIFEST["workloads"]:
        c = Cell(MANIFEST, cell["name"])
        assert set(THREE) <= {m["name"] for m in c.per_layer}
        assert "tpot_p50_ms" in {m["name"] for m in c.end_to_end}
    for name in THREE:
        assert callable(reader(name))


def test_the_traced_rehearsal_carries_all_three(checkout):  # noqa: F811
    """The toy runs the real scheduler on the CPU, so its traced line
    holds the three readings, its info line the tables, and every tick
    record the account; the untraced run keeps the same records."""
    r = run(checkout, "--workload", "tiny.batch", "--seed", "54",
            "--seconds", "4", "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = last_json(r)
    assert out["correct"] is True, r.stderr[-3000:]
    m = out["metrics"]
    assert set(THREE) <= set(m)
    for name, unit in THREE.items():
        assert m[name]["unit"] == unit and m[name]["value"] >= 0.0
    assert m["tick_off_cpu_share"]["value"] <= 100.0
    assert m["stall_ticks"]["value"] == len(info["stalled_ticks"])
    assert info["off_cpu_ticks"] > 0 and info["off_cpu_by_span"]
    assert 0.0 <= info["gc_share"] <= 100.0
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    assert ticks
    assert sum(t["off_cpu_by"] is not None for t in ticks) \
        >= info["off_cpu_ticks"] > 0
    for t in ticks:
        assert 0.0 <= t["cpu_s"] <= t["wall_s"] + 1e-3
        if t["off_cpu_by"] is not None:
            assert sum(t["off_cpu_by"].values()) == pytest.approx(
                t["wall_s"] - t["cpu_s"], abs=2e-3)
        assert (t["proc_cpu_s"] is None) == (t["off_cpu_by"] is None)
        assert t["gc_s"] >= 0.0
        assert t["run_delay_s"] is None or t["run_delay_s"] >= 0.0
        assert t["stall"] is None or set(t["stall"]) == {
            "phase", "span", "cause", "excess_s"}
    # the server counts its collections: some tick of a whole run saw one
    assert sum(t["gc_collections"] for t in ticks) > 0
