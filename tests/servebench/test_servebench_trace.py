"""The reduction from a profiler trace to numbers: on a trace written
out by hand, where every number is known, and on a small trace recorded
on the chip (cut out of a capture of `butterfly serve` by
make_recorded_trace.py).

Reading a trace needs `jax.profiler.ProfileData`, which loads no device.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from servebench.xplane import clean, find_trace, reduce_trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "files" / "recorded.xplane.pb"

# two device planes; times in picoseconds from the line's start.
#   chip 0 ops: while.1 [0, 10us) holding fusion.2 [1, 4us) and
#   all-reduce.3 [5, 7us); then fusion.2 [30, 35us)
#   chip 1 ops: fusion.2 [0, 20us)
BY_HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 30000000 duration_ps: 5000000 }
    events { metadata_id: 5 offset_ps: 36000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "all-reduce.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_block(123)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_small(9)" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "http" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 40000000 } }
  lines { id: 2 name: "ticker" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "$scheduler.py:800 tick" } }
  event_metadata { key: 2 value { id: 2 name: "$scheduler.py:1825 _mixed_block" } }
  event_metadata { key: 3 value { id: 3 name: "$selectors.py:1 poll" } } }
"""


@pytest.fixture(scope="module")
def profile_data():
    from jax.profiler import ProfileData
    return ProfileData


def test_reduction_of_a_trace_written_by_hand(profile_data, tmp_path):
    blob = profile_data.text_proto_to_serialized_xspace(BY_HAND)
    f = tmp_path / "plugins" / "profile" / "x" / "hand.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(blob)
    s = reduce_trace(profile_data.from_file(find_trace(str(tmp_path))))
    assert s["devices"] == 2
    # busy: chip 0 has 10 + 5 us, chip 1 has 20 us; the mean
    assert s["busy_s"] == pytest.approx(17.5e-6)
    assert s["window_s"] == pytest.approx(37e-6)
    ops = {name: (sec, n) for name, sec, n in s["ops"]}
    # self time: the while keeps 10 - 3 - 2 us; sums are means over chips
    assert ops["while.1"] == (pytest.approx(2.5e-6), 1)
    assert ops["fusion.2"] == (pytest.approx((3 + 5 + 20) / 2 * 1e-6), 3)
    assert ops["all-reduce.3"] == (pytest.approx(1e-6), 1)
    assert sum(sec for sec, _ in ops.values()) == pytest.approx(s["busy_s"])
    assert s["modules"]["jit_block"] == pytest.approx([10e-6, 5e-6])
    # the 20 us gap on chip 0 belongs to what the tick thread was in
    assert s["tick_thread"] == "ticker"
    assert s["idle_gaps"] == [["_scheduler.py:1825__mixed_block",
                               pytest.approx(20e-6)]]


def test_names_are_cleaned():
    assert clean("$scheduler.py:800 tick") == "_scheduler.py:800_tick"
    assert len(clean("x" * 300)) == 64


def test_no_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        find_trace(str(tmp_path))


def test_reduction_of_the_recorded_trace(profile_data):
    """2.1 s of `mistral7b.batch` on the chip (PR 23): one whole mixed
    block (948 ms) and one flush of the write window lie inside it."""
    s = reduce_trace(profile_data.from_file(str(RECORDED)))
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(2.0997, abs=1e-3)
    assert s["busy_s"] == pytest.approx(2.0214, abs=1e-3)
    assert sum(sec for _, sec, _ in s["ops"]) == pytest.approx(s["busy_s"], rel=1e-6)
    # the block's largest operation is the f32 fusion over the write
    # window's scales; the mixed block holds no Mosaic call
    top = s["ops"][0]
    assert top[0].startswith("_fusion.303____f32_32_8_32_4_") and \
        top[1] == pytest.approx(0.3138, abs=1e-3)
    assert not any("paged_att" in n for n, _, _ in s["ops"])
    block = max(s["modules"].values(), key=sum)
    assert block == pytest.approx([0.9477], abs=1e-3)
    assert s["modules"]["jit_flush_paged_window"] == pytest.approx([0.0495], abs=1e-3)
    assert s["tick_thread"] == "python3"
    owners = dict(s["idle_gaps"])
    assert owners and sum(owners.values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=0.05)
