"""`flush_ms_p50` (PR 35) on a trace written out by hand: two whole runs
of the flush and one that the capture's edge cut; None on what holds no
flush; and its entry in the manifest."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from servebench.manifest import Cell, load_manifest  # noqa: E402
from servebench.xplane import reduce_trace  # noqa: E402
from test_servebench_trace import module_line, profile_data  # noqa: E402,F401

#: microseconds; ids 1 mixed block, 2 flush, 3 decode block. The capture
#: starts inside a flush and keeps its last 1.2 ms under the flush's
#: name; the flushes behind the two blocks lasted 2.9 and 3.3 ms
CUT_WHOLE_WHOLE = [(2, 0, 1_200), (1, 1_300, 116_000), (2, 117_400, 2_900),
                   (3, 120_400, 106_000), (2, 226_500, 3_300),
                   (1, 229_900, 116_000)]


def reader():
    return Cell(load_manifest(ROOT), "mistral7b.batch", ROOT).reader(
        "flush_ms_p50")


def ctx_of(runs, profile_data, tmp_path):  # noqa: F811
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(profile_data.text_proto_to_serialized_xspace(
        module_line(runs)))
    return SimpleNamespace(trace=reduce_trace(profile_data.from_file(str(f))))


def test_median_of_the_whole_flushes(profile_data, tmp_path):  # noqa: F811
    ctx = ctx_of(CUT_WHOLE_WHOLE, profile_data, tmp_path)
    assert reader()(ctx) == pytest.approx(3.1)


@pytest.mark.parametrize("runs", [
    # blocks and no flush: a program that keeps no window
    [(1, 0, 116_000), (3, 116_100, 106_000), (1, 222_200, 116_000)],
    # one flush, and it starts with the capture: none is surely whole
    [(2, 0, 1_200), (1, 1_300, 116_000)],
], ids=["no-flush", "only-a-cut-one"])
def test_none_where_no_whole_flush_ran(runs, profile_data,  # noqa: F811
                                       tmp_path):
    assert reader()(ctx_of(runs, profile_data, tmp_path)) is None


def test_none_without_a_trace():
    assert reader()(SimpleNamespace(trace={})) is None
    assert reader()(SimpleNamespace(trace=None)) is None


def test_its_entry_reads_every_cell():
    """No `workloads` list: every cell flushes its window, so every
    cell's traced line reports it."""
    entry = next(m for m in load_manifest(ROOT)["per_layer"]
                 if m["name"] == "flush_ms_p50")
    assert entry == {"name": "flush_ms_p50", "unit": "ms", "better": "lower",
                     "source": "device_trace",
                     "layer": "cache manager (cache/)",
                     "moves": "tpot_p50_ms"}
