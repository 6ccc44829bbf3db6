"""``ttft_relay_ms`` (and the five data-only hops beside it) on a made-up
context: three client records, an engine whose families grew by known sums."""

import dataclasses
from typing import Optional

import pytest

from conftest import BENCH
from harness import layers

HOPS = {
    "ttft_upstream_ms": "tpu:request_upstream_seconds",
    "ttft_admit_ms": "tpu:request_admit_seconds",
    "ttft_pending_ms": "tpu:request_pending_seconds",
    "ttft_prefill_ms": "tpu:prefill_time_seconds",
    "ttft_write_ms": "tpu:first_token_write_seconds",
}


@dataclasses.dataclass
class Rec:
    sent: float
    first: Optional[float]
    phase: str = "measure"


def _prom(count, **sums):
    out = {}
    for family, total in sums.items():
        out[f"tpu:{family}_seconds_sum"] = total
        out[f"tpu:{family}_seconds_count"] = count
    return out


# Three requests in the window, mean client TTFT 0.200 s; one warm-up
# record, one that never got a token and one whose token fell after the
# window are left out, as router_added_ttft leaves them out.
RECORDS = [Rec(10.0, 10.15), Rec(11.0, 11.20), Rec(12.0, 12.25),
           Rec(5.0, 5.5, phase="warmup"), Rec(13.0, None), Rec(54.0, 56.0)]
BEFORE = _prom(10, request_upstream=0.1, ttft=1.0, first_token_write=0.01,
               request_admit=0.02, request_pending=0.5, prefill_time=0.6,
               queue_time=0.05)
# Over the window, per request: upstream 3 ms, ttft 190 ms (admit 2 +
# pending 60 + queue 48 + prefill 80), first write 2 ms.
AFTER = _prom(13, request_upstream=0.1 + 0.009, ttft=1.0 + 0.570,
              first_token_write=0.01 + 0.006, request_admit=0.02 + 0.006,
              request_pending=0.5 + 0.180, prefill_time=0.6 + 0.240,
              queue_time=0.05 + 0.144)


def _context(before=BEFORE, after=AFTER, records=RECORDS):
    return layers.Context(
        cell={"name": "m7b-int8.chat-steady", "config": "mistral-7b-int8",
              "chips": 1}, config={},
        records=records, late_ms=[],
        got={"t0": 9.0, "seconds": 45.0,
             "before": {"prom": before}, "after": {"prom": after}},
        summary={}, dirs=[BENCH])


def _read(ctx, name):
    return layers.read_all(ctx, [name])[name]


def test_relay_is_what_neither_engine_stretch_holds():
    # 200 - (3 + 190 + 2) = 5 ms: client -> router, and the first event's
    # way back through the relay.
    assert _read(_context(), "ttft_relay_ms") == pytest.approx(5.0)
    # The split copy reads the base name's file.
    assert _read(_context(), "ttft_relay_ms.chat-steady") == pytest.approx(5.0)


def test_the_parts_tile_router_added_ttft():
    ctx = _context()
    added = _read(ctx, "router_added_ttft_ms")
    parts = sum(_read(ctx, n) for n in (
        "ttft_upstream_ms", "ttft_write_ms", "ttft_relay_ms"))
    assert added == pytest.approx(10.0) and parts == pytest.approx(added)


@pytest.mark.parametrize("name,want", [
    ("ttft_upstream_ms", 3.0), ("ttft_admit_ms", 2.0),
    ("ttft_pending_ms", 60.0), ("ttft_prefill_ms", 80.0),
    ("ttft_write_ms", 2.0), ("ttft_pending_ms.chat-steady", 60.0),
])
def test_data_only_hops_read_their_family(name, want):
    assert _read(_context(), name) == pytest.approx(want)


@pytest.mark.parametrize("family", [
    "tpu:request_upstream_seconds", "tpu:first_token_write_seconds"])
def test_a_program_without_the_family_reads_nothing(family):
    """The parent commit has no such family: None, and nothing raised."""
    strip = lambda prom: {k: v for k, v in prom.items()
                          if not k.startswith(family)}
    ctx = _context(strip(BEFORE), strip(AFTER))
    assert _read(ctx, "ttft_relay_ms") is None
    for name, fam in HOPS.items():
        if fam == family:
            assert _read(ctx, name) is None


def test_no_observation_or_no_request_reads_nothing():
    # The header never arrived (an engine reached without the router).
    after = dict(AFTER, **{
        "tpu:request_upstream_seconds_sum": 0.1,
        "tpu:request_upstream_seconds_count": 10})
    assert _read(_context(after=after), "ttft_relay_ms") is None
    assert _read(_context(records=[]), "ttft_relay_ms") is None
