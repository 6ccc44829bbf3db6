"""``readers/request_gaps.py`` and ``readers/program_share.py`` on a made-up
windows payload and trace: the cut at the window's start and at the
profiler's start, ``None`` where no row qualifies, each ``part`` x ``stat``."""

import pytest

from conftest import BENCH, benchmark_file
from harness import layers
from readers import program_share, request_gaps

T0 = 1_800_000_000.0


def _rec(dispatched, collected, finished=(), behind_s=None, kind="decode"):
    w = {"kind": kind, "dispatched_at": T0 + dispatched,
         "collected_at": T0 + collected}
    if finished:
        w["finished"] = [list(row) for row in finished]
    if behind_s is not None:
        w["behind_s"] = behind_s
    return w


# [seq_id, tokens, span_s, own_s, prefill_s, prefills, rest_s]
A = ["a", 101, 1.00, 0.80, 0.19, 12, 0.01]    # 10 / 8 / 1.9 / 0.1 ms a token
B = ["b", 51, 1.00, 0.60, 0.38, 20, 0.02]     # 20 / 12 / 7.6 / 0.4
C = ["c", 11, 0.30, 0.30, 0.00, 0, 0.00]      # 30 / 30 / 0 / 0
RECORDS = [
    _rec(-0.2, 0.5, [["early", 41, 1.0, 1.0, 0.0, 0, 0.0]]),  # began before
    _rec(5.0, 5.1, [A], behind_s=0.040, kind="prefill"),
    _rec(6.0, 6.1, [B, ["one", 1, 0.0, 0.0, 0.0, 0, 0.0]]),
    _rec(7.0, 7.1, behind_s=0.0, kind="prefill"),
    _rec(8.0, 8.1, [C], behind_s=0.020, kind="prefill"),
    _rec(38.9, 39.5, [["late", 41, 1.0, 1.0, 0.0, 0, 0.0]],
         behind_s=0.5, kind="prefill"),               # closed in the trace
    _rec(46.0, 46.1, [["after", 41, 1.0, 1.0, 0.0, 0, 0.0]]),
]


def _context(records=RECORDS, trace_wall=(T0 + 39.0, T0 + 42.0), trace=None):
    got = {"wall_t0": T0, "seconds": 45.0, "windows": {"windows": records}}
    if trace_wall:
        got["trace_wall"] = list(trace_wall)
    return layers.Context(
        cell={"name": "m7b-int8.chat-steady", "config": "mistral-7b-int8",
              "chips": 1}, config={}, records=[], late_ms=[], got=got,
        summary={}, dirs=[BENCH], trace=trace)


@pytest.mark.parametrize("part,stat,want", [
    ("span", "mean", 20.0), ("span", "p50", 20.0), ("span", "p95", 29.0),
    ("own", "mean", (8 + 12 + 30) / 3), ("own", "p95", 28.2),
    ("prefill", "mean", (1.9 + 7.6) / 3), ("prefill", "p50", 1.9),
    ("prefill", "p95", 7.03),
    ("behind", "mean", 20.0), ("behind", "p50", 20.0), ("behind", "p95", 38.0),
])
def test_each_part_and_statistic(part, stat, want):
    got = request_gaps.read(_context(), {"part": part, "stat": stat})
    assert got == pytest.approx(want, rel=1e-9)


def test_the_parts_means_add_up_to_the_spans():
    """The span's mean less the two parts that have an entry is the mean of
    the rows' ``rest_s`` a token (0.1, 0.4 and 0 ms here)."""
    ctx = _context()
    mean = {p: request_gaps.read(ctx, {"part": p, "stat": "mean"})
            for p in ("span", "own", "prefill")}
    assert mean["span"] - mean["own"] - mean["prefill"] == pytest.approx(
        0.5 / 3, abs=1e-9)


def test_without_a_trace_the_cut_is_the_windows_end():
    ctx = _context(trace_wall=None)
    # "late" counts now (closed at 39.5 s of 45); "after" still does not
    assert request_gaps.read(ctx, {"part": "span", "stat": "mean"}) == (
        pytest.approx((10 + 20 + 30 + 25) / 4))
    assert request_gaps.read(ctx, {"part": "behind", "stat": "mean"}) == (
        pytest.approx((40 + 0 + 20 + 500) / 4))


@pytest.mark.parametrize("part", ["span", "own", "prefill", "behind"])
def test_none_where_no_row_qualifies(part):
    """A program from before the account serves neither field; an empty
    ring, a window with no finished request."""
    bare = [_rec(5.0, 5.1), _rec(6.0, 6.1, kind="prefill")]
    for records in ([], bare, RECORDS[:1] + RECORDS[-2:]):
        assert request_gaps.read(
            _context(records), {"part": part, "stat": "mean"}) is None


def test_program_share_is_the_named_programs_part_of_the_span():
    trace = {"window_s": 3.0, "programs": [
        ["window_fn", 2.2, 40], ["prefill_fn", 0.6, 30], ["_unknown", 0.1, 5]]}
    ctx = _context(trace=trace)
    assert program_share.read(ctx, {"program": "prefill_fn"}) == (
        pytest.approx(20.0))
    assert program_share.read(ctx, {"program": "window_fn"}) == (
        pytest.approx(100 * 2.2 / 3.0))
    assert program_share.read(ctx, {"program": "mixed_fn"}) is None
    assert program_share.read(_context(), {"program": "prefill_fn"}) is None
    assert program_share.read(
        _context(trace={"window_s": None, "programs": []}),
        {"program": "prefill_fn"}) is None


NEW = ("tpot_engine_p95_ms", "tpot_own_ms", "tpot_behind_prefill_ms",
       "tpot_behind_prefill_p95_ms", "prefill_busy_share", "ttft_behind_ms",
       "ttft_behind_ms.chat-steady")


def test_the_seven_entries_read_through_the_harness():
    bench = benchmark_file()
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(NEW)
    for name in NEW[:5]:
        assert entries[name]["workloads"] == cells, name
        assert entries[name]["moves"] == "tpot_p95_ms"
    assert entries["ttft_behind_ms"]["workloads"] == cells[1:]
    assert entries["ttft_behind_ms"]["moves"] == "out_tok_s"
    assert entries["ttft_behind_ms.chat-steady"]["workloads"] == cells[:1]
    assert entries["ttft_behind_ms.chat-steady"]["moves"] == "ttft_mean_ms"
    trace = {"window_s": 3.0, "programs": [["prefill_fn", 0.6, 30]],
             "modules": []}
    values = layers.read_all(_context(trace=trace), list(NEW))
    assert values["tpot_engine_p95_ms"] == pytest.approx(29.0)
    assert values["tpot_own_ms"] == pytest.approx((8 + 12 + 30) / 3)
    assert values["tpot_behind_prefill_ms"] == pytest.approx(9.5 / 3)
    assert values["prefill_busy_share"] == pytest.approx(20.0)
    assert values["ttft_behind_ms"] == values["ttft_behind_ms.chat-steady"] \
        == pytest.approx(20.0)
    assert not layers.is_count("tpot_own_ms", [BENCH], "mistral-7b-int8")
    # a program from before the account: every reader of the records
    # returns nothing, and the line leaves the entries out
    old = layers.read_all(
        _context([_rec(5.0, 5.1), _rec(6.0, 6.1, kind="prefill")]),
        [n for n in NEW if n != "prefill_busy_share"])
    assert set(old.values()) == {None}
