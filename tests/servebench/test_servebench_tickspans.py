"""The tick's spans beside the device's idle gaps (servebench/
tickspans.py) on a trace written out by hand, where every number is
known; the readers that use it and the other readers of PR 24 on made-up
contexts, each returning None on what an older program leaves (a trace
whose block is `jit__unknown`, tick records without `program`); and the
two that read tick records alone in a traced rehearsal on the CPU.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench import tickspans  # noqa: E402
from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.xplane import reduce_trace  # noqa: E402
from test_servebench_run import checkout, last_json, run  # noqa: E402,F401
from test_servebench_trace import BY_HAND as OLD_STYLE  # noqa: E402

# chip 0, times in microseconds (offsets are picoseconds):
#   ops      A [0,100) B [130,200) C [220,300) D [350,400) E [410,500)
#            F [540,600) G [602,700)
#   idle     [100,130) inside bf.tick.drain.fetch       30 us
#            [200,220) inside bf.tick.drain.emit        20 us
#            [300,350) inside bf.loop.wait              50 us
#            [400,410) inside no bf. span               10 us
#            [500,540) inside bf.tick, no span below it 40 us
#            [600,602) shorter than MIN_GAP_S           not counted
# the tick thread also holds the Python tracer's events, which own nothing
BY_HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 1 offset_ps: 130000000 duration_ps: 70000000 }
    events { metadata_id: 1 offset_ps: 220000000 duration_ps: 80000000 }
    events { metadata_id: 1 offset_ps: 350000000 duration_ps: 50000000 }
    events { metadata_id: 1 offset_ps: 410000000 duration_ps: 90000000 }
    events { metadata_id: 1 offset_ps: 540000000 duration_ps: 60000000 }
    events { metadata_id: 1 offset_ps: 602000000 duration_ps: 98000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 300000000 }
    events { metadata_id: 2 offset_ps: 350000000 duration_ps: 150000000 }
    events { metadata_id: 3 offset_ps: 540000000 duration_ps: 160000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "jit_bf_mixed_block_win(12)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_bf_decode_block_win(3)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "http" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 700000000 } }
  lines { id: 2 name: "ticker" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 700000000 }
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 140000000 }
    events { metadata_id: 2 offset_ps: 95000000 duration_ps: 130000000 }
    events { metadata_id: 3 offset_ps: 98000000 duration_ps: 37000000 }
    events { metadata_id: 4 offset_ps: 140000000 duration_ps: 82000000 }
    events { metadata_id: 5 offset_ps: 290000000 duration_ps: 70000000 }
    events { metadata_id: 1 offset_ps: 480000000 duration_ps: 80000000 }
    events { metadata_id: 6 offset_ps: 545000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bf.tick" } }
  event_metadata { key: 2 value { id: 2 name: "bf.tick.drain_oldest" } }
  event_metadata { key: 3 value { id: 3 name: "bf.tick.drain.fetch" } }
  event_metadata { key: 4 value { id: 4 name: "bf.tick.drain.emit" } }
  event_metadata { key: 5 value { id: 5 name: "bf.loop.wait" } }
  event_metadata { key: 6 value { id: 6 name: "bf.tick.mixed" } }
  event_metadata { key: 9 value { id: 9 name: "$scheduler.py:800 tick" } } }
"""
US = 1e-6


@pytest.fixture(scope="module")
def profile_data():
    from jax.profiler import ProfileData
    return ProfileData


def write(profile_data, text, directory):
    f = directory / "plugins" / "profile" / "x" / "hand.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(profile_data.text_proto_to_serialized_xspace(text))
    return f


def test_idle_gaps_by_span_on_a_trace_written_by_hand(profile_data, tmp_path):
    s = tickspans.reduce_spans(profile_data.from_file(
        str(write(profile_data, BY_HAND, tmp_path))))
    assert s["tick_thread"] == "ticker" and s["ticks"] == 2
    assert dict(s["idle_by_span"]) == {
        "bf.loop.wait": pytest.approx(50 * US),
        "bf.tick": pytest.approx(40 * US),
        "bf.tick.drain.fetch": pytest.approx(30 * US),
        "bf.tick.drain.emit": pytest.approx(20 * US),
        "(outside tick)": pytest.approx(10 * US)}
    assert [n for n, _ in s["idle_by_span"]][0] == "bf.loop.wait"


def test_a_trace_without_spans_gives_no_table(profile_data, tmp_path):
    s = tickspans.reduce_spans(profile_data.from_file(
        str(write(profile_data, OLD_STYLE, tmp_path))))
    assert s["idle_by_span"] is None and s["ticks"] == 0


def reader(name):
    return Cell(load_manifest(ROOT), "mistral7b.batch", ROOT).reader(name)


def traced_ctx(profile_data, text, root, monkeypatch):
    """A context as run.py makes it, over a trace under `root`."""
    monkeypatch.setattr(tickspans, "ROOT", root)
    info = {"workload": "cell", "seed": 7}
    f = write(profile_data, text,
              root / "chiprun_out" / "servebench" / "cell-s7-t1" / "trace")
    return SimpleNamespace(trace=reduce_trace(profile_data.from_file(str(f))),
                           info=info)


def test_the_idle_shares_add_up_to_the_idle_share(profile_data, tmp_path,
                                                  monkeypatch):
    ctx = traced_ctx(profile_data, BY_HAND, tmp_path, monkeypatch)
    host = reader("idle_host_share")(ctx)
    # the first reader ran the child; the table is in the info line
    assert dict(ctx.info["idle_by_span"])["bf.tick.drain.fetch"] == \
        pytest.approx(30 * US)
    json.dumps(ctx.info)
    (tmp_path / "chiprun_out" / "servebench" / "cell-s7-t1"
     / "idle_by_span.json").unlink()          # and is not made again
    fetch = reader("idle_fetch_share")(ctx)
    outside = reader("idle_outside_tick_share")(ctx)
    assert host == pytest.approx(100 * 60 / 700)      # emit + bare tick
    assert fetch == pytest.approx(100 * 30 / 700)
    assert outside == pytest.approx(100 * 60 / 700)   # wait + no span
    idle = reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * 152 / 700)
    assert abs(host + fetch + outside - idle) < 0.5
    assert reader("mixed_block_ms_p50")(ctx) == pytest.approx(0.225)
    assert reader("decode_block_ms_p50")(ctx) == pytest.approx(0.160)
    # block_ms_p50 now picks a named module
    assert reader("block_ms_p50")(ctx) == pytest.approx(0.225)


@pytest.mark.parametrize("name", [
    "idle_host_share", "idle_fetch_share", "idle_outside_tick_share",
    "mixed_block_ms_p50", "decode_block_ms_p50"])
def test_device_readers_give_none_on_an_old_style_trace(
        name, profile_data, tmp_path, monkeypatch):
    """A program that writes no span and names no block (`jit_block`,
    `jit__unknown`): nothing to read, and no error."""
    ctx = traced_ctx(profile_data, OLD_STYLE, tmp_path, monkeypatch)
    ctx.trace["modules"]["jit__unknown"] = [0.9]
    assert reader(name)(ctx) is None
    assert reader("block_ms_p50")(ctx) == pytest.approx(900.0)


@pytest.mark.parametrize("name", [
    "idle_host_share", "idle_fetch_share", "idle_outside_tick_share",
    "mixed_block_ms_p50", "decode_block_ms_p50"])
def test_device_readers_give_none_without_a_trace(name, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(tickspans, "ROOT", tmp_path)
    ctx = SimpleNamespace(trace={}, info={"workload": "cell", "seed": 7})
    assert reader(name)(ctx) is None
    ctx.trace = {"window_s": 3.0, "modules": {}}   # a trace, no file
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", ["decode_block_ms_p50", "lock_wait_p50_ms"])
def test_a_reader_no_cell_can_feed_has_no_entry(name):
    """The driver refuses a traced line that lacks a metric the manifest
    gives the cell. Both batch cells keep a request waiting through the
    whole window, so no decode block runs in them, and the front end's
    metrics wait for the chat cell: the readers are there, the entries
    are not."""
    assert callable(reader(name))
    assert not any(m["name"] == name
                   for m in load_manifest(ROOT)["per_layer"])


def tick(seq, t_wall, wall_s, **more):
    return dict(seq=seq, t_wall=t_wall, wall_s=wall_s, **more)


def ticks_ctx(ticks):
    return SimpleNamespace(ticks=ticks, w0=100.0, w1=145.0,
                           wall_minus_mono=1000.0)


def test_mixed_tick_share_and_compiles_in_window():
    ticks = [tick(1, 1090.0, 9.0, program="bf_mixed_block_win", compiles=70),
             tick(2, 1101.0, 0.9, program="bf_mixed_block_win", compiles=70),
             tick(3, 1102.0, 0.2, program="bf_decode_block_win", compiles=71),
             tick(3, 1102.0, 0.2, program="bf_decode_block_win", compiles=71),
             tick(4, 1103.0, 0.1, program=None, compiles=73),
             tick(5, 1150.0, 9.0, program="bf_mixed_block_win", compiles=99)]
    ctx = ticks_ctx(ticks)
    # ticks 2, 3 (once) and 4 are the window's
    assert reader("mixed_tick_share")(ctx) == pytest.approx(100 * 0.9 / 1.2)
    assert reader("compiles_in_window")(ctx) == 3.0
    # an older program's tick records carry neither field
    old = ticks_ctx([tick(2, 1101.0, 0.9), tick(3, 1102.0, 0.2)])
    assert reader("mixed_tick_share")(old) is None
    assert reader("compiles_in_window")(old) is None
    assert reader("mixed_tick_share")(ticks_ctx([])) is None


def test_lock_wait_p50_ms():
    """Written beside front_ms_p50 and queue_wait_p50_ms; like them it
    has no entry until the chat cell is in the manifest."""
    def rec(rid, t, **submit):
        return {"request_id": rid, "events": [
            dict(name="submit", t=t, **submit), dict(name="admit", t=t + 1)]}
    streams = [SimpleNamespace(rid=r, due=d)
               for r, d in (("a", 101.0), ("b", 102.0), ("c", 103.0),
                            ("late", 150.0), ("none", 104.0))]
    ctx = SimpleNamespace(
        streams=streams, w0=100.0, w1=145.0, wall_minus_mono=0.0,
        requests={"t0_monotonic": 0.0, "t0_wall": 0.0, "requests": [
            rec("a", 101.5, lock_wait_s=0.010, t_recv=101.4),
            rec("b", 102.5, lock_wait_s=0.300, t_recv=102.1),
            rec("c", 103.5, lock_wait_s=0.020, t_recv=103.4),
            rec("late", 150.5, lock_wait_s=9.0, t_recv=141.0)]})
    assert reader("lock_wait_p50_ms")(ctx) == pytest.approx(20.0)
    assert not any(m["name"] == "lock_wait_p50_ms"
                   for m in load_manifest(ROOT)["per_layer"])
    # an older program's submit events carry no lock_wait_s
    ctx.requests["requests"] = [rec("a", 101.5), rec("b", 102.5)]
    assert reader("lock_wait_p50_ms")(ctx) is None
    ctx.requests = {}
    assert reader("lock_wait_p50_ms")(ctx) is None


def test_tick_record_readers_print_in_a_traced_rehearsal(checkout):  # noqa: F811
    """`mixed_tick_share` and `compiles_in_window` read tick records
    alone, so the traced rehearsal on the CPU prints them; the readers
    of the device trace are skipped and tickspans.py is not started."""
    r = run(checkout, "--workload", "tiny.batch", "--seed", "5",
              "--seconds", "4", "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    info, out = last_json(r)
    assert out["correct"] is True, r.stderr[-3000:]
    m = out["metrics"]
    assert 0.0 <= m["mixed_tick_share"]["value"] <= 100.0
    assert m["mixed_tick_share"]["unit"] == "%"
    assert m["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    assert not {"idle_host_share", "idle_fetch_share", "mixed_block_ms_p50",
                "idle_outside_tick_share", "decode_block_ms_p50"} & set(m)
    assert "idle_by_span" not in info
    assert not list((checkout / "chiprun_out").rglob("idle_by_span.json"))
    ticks = json.loads(next((checkout / "chiprun_out").rglob("ticks.json"))
                       .read_text())
    assert {t["program"] for t in ticks} <= {
        None, "bf_mixed_block_win", "bf_decode_block_win"}
    assert all(t["lock_s"] >= 0.0 for t in ticks)
