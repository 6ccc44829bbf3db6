"""``readers/window_steps.py`` on made-up flight records: the windows alone
(a ``cut`` on a decode record), inside the run's window alone, and None --
not 0 -- from an engine whose records have no ``cut``."""

import pytest

from conftest import BENCH, benchmark_file
from harness import layers
from readers import window_steps

T0 = 1_800_000_000.0


def _rec(at, k, cut=None, kind="decode"):
    w = {"kind": kind, "k": k, "rows": 3, "dispatched_at": T0 + at}
    if cut is not None:
        w["cut"] = cut
    return w


RECORDS = [
    _rec(-1.0, 8, "cap"),                 # before the window
    _rec(1.0, 8, "cap"),
    _rec(2.0, 3, "finish"),
    _rec(3.0, 1, "finish"),
    _rec(4.0, 2, "host"),
    _rec(5.0, 1, kind="prefill"),         # not a window
    _rec(6.0, 1),                         # a single step under a waiting head
    _rec(46.0, 2, "finish"),              # after it
]


def _context(records):
    return layers.Context(
        cell={"name": "m7b-int8.chat-steady", "config": "mistral-7b-int8",
              "chips": 1}, config={}, records=[], late_ms=[],
        got={"wall_t0": T0, "seconds": 45.0, "windows": {"windows": records}},
        summary={}, dirs=[BENCH])


@pytest.mark.parametrize("what,want", [
    ("mean", (8 + 3 + 1 + 2) / 4), ("finish_share", 50.0)])
def test_the_windows_inside_the_window(what, want):
    assert window_steps.read(_context(RECORDS), {"what": what}) == want


@pytest.mark.parametrize("what", ["mean", "finish_share"])
def test_an_engine_without_the_field_reads_none(what):
    before = [{k: v for k, v in w.items() if k != "cut"} for w in RECORDS]
    assert window_steps.read(_context(before), {"what": what}) is None
    assert window_steps.read(_context([]), {"what": what}) is None


def test_an_unknown_what_is_an_error():
    with pytest.raises(ValueError):
        window_steps.read(_context(RECORDS), {"what": "median"})


@pytest.mark.parametrize("name,what", [
    ("window_steps_mean", "mean"), ("window_finish_cut_share", "finish_share")])
def test_the_entries_find_their_files_in_every_cell(name, what):
    bench = benchmark_file()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    for cell in bench["workloads"]:
        spec = layers.spec_of(name, [BENCH], cell["config"])
        assert spec["reader"] == "window_steps" and spec["count"]
        assert spec["args"] == {"what": what}
