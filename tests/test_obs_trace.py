"""Unit tests for the obs/ subsystem: histograms, traceparent handling,
tracer ring-buffer bounds, and the timeline join math."""

import threading

from production_stack_tpu.obs.histogram import (
    Histogram,
    render_histogram,
    render_labeled_histograms,
)
from production_stack_tpu.obs.trace import (
    Tracer,
    make_traceparent,
    new_trace_id,
    parse_traceparent,
)
from production_stack_tpu.router.routers.debug_router import join_timelines


def test_histogram_buckets_and_quantile():
    h = Histogram(bounds=(0.01, 0.1, 1.0))
    for v in [0.005] * 50 + [0.05] * 40 + [0.5] * 9 + [5.0]:
        h.observe(v)
    assert h.count == 100
    assert abs(h.sum - (0.25 + 2.0 + 4.5 + 5.0)) < 1e-9
    # p50 inside the first bucket, p95 inside the third.
    assert 0.0 < h.quantile(0.50) <= 0.01
    assert 0.1 < h.quantile(0.95) <= 1.0
    # The +Inf bucket claims no more than the last finite bound.
    assert h.quantile(0.999) == 1.0
    assert Histogram().quantile(0.95) == 0.0  # empty -> 0


def test_histogram_render_is_cumulative_and_parseable():
    from prometheus_client.parser import text_string_to_metric_families

    h = Histogram(bounds=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(7.0)
    text = render_histogram("tpu:test_seconds", h)
    fams = list(text_string_to_metric_families(text))
    assert len(fams) == 1 and fams[0].type == "histogram"
    buckets = {
        s.labels["le"]: s.value
        for s in fams[0].samples
        if s.name.endswith("_bucket")
    }
    assert buckets["+Inf"] == 3
    # Cumulative monotone.
    values = [buckets[k] for k in ("0.01", "0.1", "+Inf")]
    assert values == sorted(values)
    count = [s for s in fams[0].samples if s.name.endswith("_count")][0]
    assert count.value == 3


def test_labeled_histogram_render():
    a, b = Histogram(bounds=(1.0,)), Histogram(bounds=(1.0,))
    a.observe(0.5)
    text = render_labeled_histograms("tpu_router:test_seconds", {"u1": a, "u2": b})
    assert 'server="u1"' in text and 'server="u2"' in text
    assert text.count("# TYPE tpu_router:test_seconds histogram") == 1


def test_histogram_thread_safety():
    h = Histogram()
    def work():
        for _ in range(1000):
            h.observe(0.01)
    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 4000


def test_traceparent_roundtrip_and_malformed():
    tid = new_trace_id()
    assert parse_traceparent(make_traceparent(tid)) == tid
    assert parse_traceparent(None) is None
    assert parse_traceparent("") is None
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-zz-11-01") is None
    assert parse_traceparent("00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None


def test_tracer_ring_and_active_bounds():
    tracer = Tracer("router", ring_size=4)
    for i in range(10):
        tracer.start(f"r{i}")
        tracer.add_span(f"r{i}", "router.queue", 0.0, 1.0)
        tracer.finish(f"r{i}", end=2.0)
    completed = tracer.completed()
    assert len(completed) == 4  # ring bound
    assert completed[0].request_id == "r9"  # newest first
    # Spans attach to completed (ring) traces too — the engine finishes a
    # trace before the server owes the detokenize span.
    tracer.add_span("r9", "engine.detokenize", 2.0, 2.1)
    assert {s.name for s in tracer.get("r9").spans} == {
        "router.queue", "engine.detokenize",
    }
    # Never-finished actives are bounded.
    for i in range(100):
        tracer.start(f"leak{i}")
    assert tracer.active_count() <= tracer.MAX_ACTIVE_FACTOR * 4


def test_tracer_byte_bound_evicts_and_counts_drops():
    """The completed ring is byte-bounded too (PR 17): long-prompt bursts
    produce records hundreds of times larger than short ones, so a
    count-only cap does not bound resident memory.  Evictions increment
    ``dropped`` (tpu:obs_trace_dropped_total) — never silent."""
    tracer = Tracer("router", ring_size=1000, ring_bytes=4096)
    for i in range(50):
        tracer.start(f"r{i}", attrs={"prompt": "x" * 512})
        tracer.add_span(f"r{i}", "router.queue", 0.0, 1.0)
        tracer.finish(f"r{i}", end=2.0)
    completed = tracer.completed()
    # Far fewer than the count bound survived; the byte bound ruled.
    assert 1 <= len(completed) < 50
    assert completed[0].request_id == "r49"  # newest always kept
    assert sum(t.approx_bytes for t in completed) <= 4096 + completed[0].approx_bytes
    assert tracer.dropped == 50 - len(completed)
    # No byte bound -> count bound only, nothing dropped at 50 records.
    unbounded = Tracer("router", ring_size=1000)
    for i in range(50):
        unbounded.start(f"r{i}", attrs={"prompt": "x" * 512})
        unbounded.finish(f"r{i}", end=2.0)
    assert len(unbounded.completed()) == 50
    assert unbounded.dropped == 0


def test_duplicate_inflight_id_supersedes_not_merges():
    """Two concurrent requests reusing one X-Request-Id must not merge
    spans into one timeline: the older active trace retires to the ring
    marked superseded."""
    tracer = Tracer("router", ring_size=4)
    first = tracer.start("dup", trace_id="aa" * 16)
    tracer.add_span("dup", "router.queue", 0.0, 1.0)
    second = tracer.start("dup", trace_id="bb" * 16)
    assert first is not second
    # First timeline preserved in the ring, flagged.
    ring = tracer.completed()
    assert len(ring) == 1
    assert ring[0].trace_id == "aa" * 16
    assert ring[0].attrs["superseded"] is True
    assert [s.name for s in ring[0].spans] == ["router.queue"]
    # New spans/finish attribute to the newest trace only.
    tracer.add_span("dup", "router.backend_connect", 1.0, 2.0)
    done = tracer.finish("dup")
    assert done.trace_id == "bb" * 16
    assert [s.name for s in done.spans] == ["router.backend_connect"]


def test_disabled_tracer_is_noop():
    tracer = Tracer("router", enabled=False)
    assert tracer.start("r1") is None
    tracer.add_span("r1", "x", 0.0, 1.0)
    assert tracer.finish("r1") is None
    assert tracer.completed() == []
    assert tracer.active_count() == 0


def test_join_timelines_phase_attribution():
    router = {
        "request_id": "r1", "trace_id": "t", "duration_s": 1.0,
        "spans": [
            {"name": "router.queue", "start": 0.0, "end": 0.1, "duration_s": 0.1},
            {"name": "router.backend_connect", "start": 0.1, "end": 0.2, "duration_s": 0.1},
            {"name": "router.stream", "start": 0.5, "end": 1.0, "duration_s": 0.5},
        ],
    }
    engine = {
        "spans": [
            {"name": "engine.queue", "start": 0.2, "end": 0.3, "duration_s": 0.1},
            {"name": "engine.prefill", "start": 0.3, "end": 0.5, "duration_s": 0.2},
            {"name": "engine.decode", "start": 0.5, "end": 1.0, "duration_s": 0.5},
        ],
    }
    joined = join_timelines(router, engine)
    # router.stream overlaps engine.decode and is excluded from phase_s.
    assert set(joined["phase_s"]) == {
        "router.queue", "router.backend_connect", "engine.queue",
        "engine.prefill", "engine.decode",
    }
    assert abs(joined["phase_sum_s"] - 1.0) < 1e-9
    assert joined["total_s"] == 1.0
    assert [s["name"] for s in joined["spans"]][:2] == [
        "router.queue", "router.backend_connect",
    ]

    # Engine unreachable: router-only join still works.
    solo = join_timelines(router, None)
    assert solo["engine"] is None
    assert set(solo["phase_s"]) == {"router.queue", "router.backend_connect"}
