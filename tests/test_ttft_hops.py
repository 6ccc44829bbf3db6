"""The time to first token, hop by hop (PR 42): a request is stamped where
it arrives (handler entry -> AsyncEngine.generate's append -> add_request on
the step thread -> first scheduled -> first token -> the stream's first
write), the spans tile its timeline, and four histogram families sit at the
same boundaries.  Also: the router's ``x-request-start`` header, the parent
span id of ``traceparent``, and the step thread's stall line."""

import asyncio
import json
import logging
import time

import pytest

from production_stack_tpu.obs import engine as obs_engine
from production_stack_tpu.obs.engine import EngineObs
from production_stack_tpu.obs.flight_recorder import WindowRecord
from production_stack_tpu.obs.trace import (
    REQUEST_START_MAX_AGE_S,
    make_request_start,
    make_traceparent,
    parse_request_start,
    parse_traceparent_ids,
)
from production_stack_tpu.router.stats import vocabulary as vocab

NEW_FAMILIES = (
    "tpu:request_upstream_seconds",
    "tpu:request_admit_seconds",
    "tpu:request_pending_seconds",
    "tpu:first_token_write_seconds",
)
NOW = 1_800_000_000.0


def approx(value):
    """Unix seconds near NOW resolve to 0.24 us in a double."""
    return pytest.approx(value, abs=2e-6)


# -- headers -----------------------------------------------------------------


@pytest.mark.parametrize("value", [
    None, "", "garbage", "t=", "t=abc", "1800000000.0", "T=1799999999.0",
    "t=nan", "t=inf", "s=1799999999.0",
])
def test_request_start_malformed_observes_nothing(value):
    assert parse_request_start(value, NOW) is None


@pytest.mark.parametrize("age,sane", [
    (0.0, True), (0.004, True), (REQUEST_START_MAX_AGE_S - 1, True),
    (REQUEST_START_MAX_AGE_S + 1, False),   # stale
    (-0.001, False), (-3600.0, False),      # in the future
])
def test_request_start_sane_window(age, sane):
    got = parse_request_start(make_request_start(NOW - age), NOW)
    if sane:
        assert got == pytest.approx(NOW - age, abs=1e-6)
    else:
        assert got is None


def test_request_start_keeps_microseconds():
    t = 1_800_000_000.123456
    assert make_request_start(t) == "t=1800000000.123456"
    assert parse_request_start(" t=1800000000.123456 ", t + 1) == t


@pytest.mark.parametrize("header,want", [
    (make_traceparent("ab" * 16, "cd" * 8), ("ab" * 16, "cd" * 8)),
    # An unsound parent id keeps the trace id.
    (f"00-{'ab' * 16}-{'0' * 16}-01", ("ab" * 16, None)),
    (f"00-{'ab' * 16}-xyz-01", ("ab" * 16, None)),
    (f"00-{'0' * 32}-{'cd' * 8}-01", (None, None)),
    ("garbage", (None, None)),
    (None, (None, None)),
])
def test_traceparent_parent_id(header, want):
    assert parse_traceparent_ids(header) == want


# -- the obs hub alone ---------------------------------------------------------


def test_new_families_render_at_zero():
    text = EngineObs().render_metrics()
    for family in NEW_FAMILIES:
        assert family in vocab.TPU_REQUEST_HISTOGRAMS.values()
        assert f"# TYPE {family} histogram" in text
        assert f"{family}_count 0" in text
    assert f"# TYPE {vocab.TPU_STEP_STALL} counter" in text
    for phase in obs_engine.PHASES:
        assert f'{vocab.TPU_STEP_STALL}{{phase="{phase}"}} 0.0' in text


class _Seq:
    seq_id = "r"
    first_scheduled_time = None
    first_token_time = None
    finish_reason = None
    num_prompt_tokens = 4
    num_generated = 2

    def __init__(self, arrival, submitted=None, admitted=None):
        self.arrival_time = arrival
        self.submitted_time = submitted
        self.admitted_time = admitted


def _spans(obs, request_id="r"):
    return obs.request_payload(request_id)["spans"]


def _drive(obs, seq, written=None, finish=NOW + 1.0, write_first=True):
    """received NOW, submitted +.004, admitted +.050, scheduled +.060,
    first token +.110, first write returned +.113."""
    seq.first_scheduled_time = NOW + 0.060
    obs.on_first_scheduled(seq, seq.first_scheduled_time)
    seq.first_token_time = NOW + 0.110
    obs.on_first_token(seq, seq.first_token_time)
    if written is not None and write_first:
        obs.on_first_written(seq.seq_id, written)
    obs.on_finish(seq, finish)
    if written is not None and not write_first:
        obs.on_first_written(seq.seq_id, written)


def test_spans_tile_and_families_add_up():
    obs = EngineObs()
    seq = _Seq(NOW, submitted=NOW + 0.004, admitted=NOW + 0.050)
    obs.start_request("r", "ab" * 16, received=NOW,
                      upstream_start=NOW - 0.002, parent_span_id="cd" * 8)
    _drive(obs, seq, written=NOW + 0.113)
    trace = obs.request_payload("r")
    assert trace["start"] == NOW
    assert trace["attrs"]["parent_span_id"] == "cd" * 8
    spans = trace["spans"]
    assert [s["name"] for s in spans] == [
        "engine.upstream", "engine.admit", "engine.pending", "engine.queue",
        "engine.prefill", "engine.first_write", "engine.decode"]
    # Ordered, disjoint, no hole: each starts where the one before ends.
    for before, after in zip(spans, spans[1:]):
        assert after["start"] == before["end"], (before, after)
    assert spans[1]["start"] == trace["start"]
    assert spans[5]["end"] == NOW + 0.113
    h = obs.request_hists
    assert h["request_upstream"].sum == approx(0.002)
    assert h["request_admit"].sum == approx(0.004)
    assert h["request_pending"].sum == approx(0.046)
    assert h["queue_time"].sum == approx(0.010)
    assert h["first_token_write"].sum == approx(0.003)
    # The repair: ttft starts at ``received`` and its parts add up to it.
    assert h["ttft"].sum == approx(0.110)
    assert h["ttft"].sum == approx(
        h["request_admit"].sum + h["request_pending"].sum
        + h["queue_time"].sum + h["prefill_time"].sum)
    assert h["e2e_latency"].sum == approx(1.0)
    # decode_time keeps its meaning (from the first token), the span tiles.
    assert h["decode_time"].sum == approx(0.890)


def test_parts_are_observed_with_ttft_so_any_scrape_adds_up():
    """A scrape between a request's first dispatch and its first token
    sees it in none of the five families: the means tile over any window."""
    obs = EngineObs()
    seq = _Seq(NOW, submitted=NOW + 0.004, admitted=NOW + 0.050)
    obs.start_request("r", None, received=NOW)
    seq.first_scheduled_time = NOW + 0.060
    obs.on_first_scheduled(seq, seq.first_scheduled_time)
    parts = ("ttft", "request_admit", "request_pending", "queue_time",
             "prefill_time")
    assert [obs.request_hists[n].count for n in parts] == [0] * 5
    # ... while its timeline already shows where it waited.
    assert [s["name"] for s in _spans(obs)] == [
        "engine.admit", "engine.pending", "engine.queue"]
    obs.on_first_token(seq, NOW + 0.110)
    assert [obs.request_hists[n].count for n in parts] == [1] * 5


def test_decode_span_follows_a_late_first_write():
    """A request that finished before its first write returned: the decode
    span is moved behind engine.first_write when that lands."""
    obs = EngineObs()
    seq = _Seq(NOW, submitted=NOW + 0.004, admitted=NOW + 0.050)
    obs.start_request("r", None, received=NOW)
    _drive(obs, seq, written=NOW + 0.113, finish=NOW + 0.5,
           write_first=False)
    spans = {s["name"]: s for s in _spans(obs)}
    assert spans["engine.decode"]["start"] == NOW + 0.113
    assert spans["engine.first_write"]["end"] == NOW + 0.113
    # ... and never past the decode's own end.
    obs2 = EngineObs()
    seq2 = _Seq(NOW, submitted=NOW + 0.004, admitted=NOW + 0.050)
    obs2.start_request("r", None, received=NOW)
    _drive(obs2, seq2, written=NOW + 0.9, finish=NOW + 0.5,
           write_first=False)
    decode = {s["name"]: s for s in _spans(obs2)}["engine.decode"]
    assert decode["start"] == decode["end"] == NOW + 0.5


def test_direct_add_request_has_no_admit_or_pending():
    """add_request called directly (a test, a lockstep follower): the
    arrival is the admission, as before."""
    obs = EngineObs()
    seq = _Seq(NOW)
    obs.start_request("r", None)
    _drive(obs, seq)
    assert [s["name"] for s in _spans(obs)] == [
        "engine.queue", "engine.prefill", "engine.decode"]
    h = obs.request_hists
    assert h["queue_time"].sum == approx(0.060)
    assert h["request_admit"].count == h["request_pending"].count == 0
    assert h["request_upstream"].count == h["first_token_write"].count == 0


def test_first_write_without_first_token_observes_nothing():
    obs = EngineObs()
    obs.start_request("r", None, received=NOW)
    obs.on_first_written("r", NOW + 1)
    obs.on_first_written("unknown", NOW + 1)
    assert obs.request_hists["first_token_write"].count == 0
    assert _spans(obs) == []


def test_tracing_off_takes_no_hop():
    obs = EngineObs(enabled=False)
    seq = _Seq(NOW, submitted=NOW + 0.004, admitted=NOW + 0.050)
    obs.start_request("r", None, received=NOW, upstream_start=NOW - 1)
    _drive(obs, seq, written=NOW + 0.113)
    assert sum(h.count for h in obs.request_hists.values()) == 0
    assert obs.tracer.active_count() == 0 and obs.tracer.completed() == []


# -- the stall line --------------------------------------------------------------


def test_stall_warns_once_and_counts(monkeypatch, caplog):
    monkeypatch.setattr(obs_engine, "STALL_S", 0.01)
    obs = EngineObs()
    rec = WindowRecord(window_id=7, kind="decode", k=8, rows=12,
                       seq_ids=("a",))
    with caplog.at_level(logging.WARNING, logger=obs_engine.__name__):
        with obs.phase("build", rec):
            pass                                    # short: no line
        with obs.phase("dispatch", rec):            # enclosing: no line
            with obs.phase("collect", rec):
                time.sleep(0.03)
        with obs.phase("wait"):
            time.sleep(0.03)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 2, lines
    assert "phase=collect" in lines[0] and "window_id=7" in lines[0]
    assert "kind=decode k=8 rows=12" in lines[0]
    assert "phase=wait" in lines[1] and "window_id=None" in lines[1]
    assert obs.step_stalls["collect"] == 1 and obs.step_stalls["wait"] == 1
    assert sum(obs.step_stalls.values()) == 2
    text = obs.render_metrics()
    assert f'{vocab.TPU_STEP_STALL}{{phase="collect"}} 1.0' in text
    assert f'{vocab.TPU_STEP_STALL}{{phase="build"}} 0.0' in text


def test_stall_off_with_tracing_off(monkeypatch, caplog):
    monkeypatch.setattr(obs_engine, "STALL_S", 0.0)
    obs = EngineObs(enabled=False)
    with caplog.at_level(logging.WARNING, logger=obs_engine.__name__):
        with obs.phase("collect"):
            time.sleep(0.002)
    assert not caplog.records and not any(obs.step_stalls.values())


# -- the real engine behind its server ---------------------------------------------


async def _engine_client(tracing=True):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    config = config_from_preset(
        "tiny-llama", **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
                         "scheduler.prefill_buckets": (16, 32),
                         "obs.tracing": tracing})
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    return engine, TestClient(server)


async def _stream(client, request_id, headers=None, max_tokens=4):
    resp = await client.post(
        "/v1/completions",
        json={"model": "tiny-llama", "prompt": "hi", "max_tokens": max_tokens,
              "ignore_eos": True, "stream": True, "temperature": 0},
        headers={"x-request-id": request_id, **(headers or {})})
    body = await resp.read()
    text = "".join(
        json.loads(line[6:])["choices"][0]["text"]
        for line in body.decode().splitlines()
        if line.startswith("data: {") and json.loads(line[6:])["choices"])
    return resp.status, text


async def test_streamed_request_six_spans_tile_under_the_routers_trace():
    engine, client = await _engine_client()
    try:
        t0 = time.time()
        status, _ = await _stream(client, "hop-1", {
            "traceparent": make_traceparent("ef" * 16, "12" * 8),
            "x-request-start": make_request_start(t0)})
        t1 = time.time()
        assert status == 200
        trace = await (await client.get("/debug/requests/hop-1")).json()
        assert trace["trace_id"] == "ef" * 16
        assert trace["attrs"]["parent_span_id"] == "12" * 8
        spans = [s for s in trace["spans"] if s["name"] != "engine.detokenize"]
        assert [s["name"] for s in spans] == [
            "engine.upstream", "engine.admit", "engine.pending",
            "engine.queue", "engine.prefill", "engine.first_write",
            "engine.decode"]
        assert spans[0]["start"] == pytest.approx(t0, abs=1e-5)
        for before, after in zip(spans, spans[1:]):
            assert after["start"] == before["end"], (before, after)
        assert spans[1]["start"] == trace["start"]      # received
        assert spans[-1]["end"] == trace["end"] <= t1
        h = engine.engine.obs.request_hists
        for name in ("request_upstream", "request_admit", "request_pending",
                     "first_token_write", "ttft", "queue_time"):
            assert h[name].count == 1, name
        assert h["ttft"].sum == pytest.approx(
            h["request_admit"].sum + h["request_pending"].sum
            + h["queue_time"].sum + h["prefill_time"].sum, abs=1e-6)
        assert h["ttft"].sum == pytest.approx(
            spans[4]["end"] - trace["start"], abs=1e-6)
        metrics = await (await client.get("/metrics")).text()
        for family in NEW_FAMILIES:
            assert f"{family}_count 1" in metrics
    finally:
        await client.close()
        await engine.close()


async def test_bad_request_start_never_fails_the_request():
    engine, client = await _engine_client()
    try:
        now = time.time()
        for i, value in enumerate([
                None, "garbage", "t=abc", make_request_start(now + 3600),
                make_request_start(now - 2 * REQUEST_START_MAX_AGE_S)]):
            headers = {} if value is None else {"x-request-start": value}
            status, _ = await _stream(client, f"bad-{i}", headers)
            assert status == 200, value
            trace = await (await client.get(f"/debug/requests/bad-{i}")).json()
            assert "engine.upstream" not in {
                s["name"] for s in trace["spans"]}, value
        h = engine.engine.obs.request_hists
        assert h["request_upstream"].count == 0
        assert h["request_admit"].count == 5
    finally:
        await client.close()
        await engine.close()


async def test_wait_behind_a_slow_pass_is_pending_not_queue():
    """A request that arrives while the step thread is inside a slow pass
    waits in the hand-over list: the wait is engine.pending, the scheduler's
    queue (engine.queue, tpu:queue_time_seconds) does not hold it, and
    Sequence.arrival_time is the handler's stamp, not the step thread's."""
    engine, client = await _engine_client()
    core = engine.engine
    try:
        import threading

        in_pass = threading.Event()
        real_collect, real_add = core.collect, core.add_request
        arrivals = {}

        def slow_collect():
            out = real_collect()
            in_pass.set()
            time.sleep(0.3)      # the device pass, as the step thread sees it
            return out

        def spy_add(request_id, **kwargs):
            real_add(request_id, **kwargs)
            arrivals[request_id] = (
                core._seqs[request_id].arrival_time, time.time())

        core.collect, core.add_request = slow_collect, spy_add
        first = asyncio.create_task(_stream(client, "slow-a", max_tokens=6))
        while not in_pass.is_set():
            await asyncio.sleep(0.005)
        t_sent = time.time()
        status, _ = await _stream(client, "slow-b", max_tokens=2)
        assert status == 200 and (await first)[0] == 200
        trace = await (await client.get("/debug/requests/slow-b")).json()
        spans = {s["name"]: s for s in trace["spans"]}
        assert spans["engine.pending"]["duration_s"] > 0.1
        assert spans["engine.queue"]["duration_s"] < 0.05
        assert spans["engine.admit"]["duration_s"] < 0.1
        arrival, admitted_about = arrivals["slow-b"]
        assert arrival == trace["start"] == spans["engine.admit"]["start"]
        assert t_sent <= arrival < t_sent + 0.1
        assert admitted_about - arrival > 0.1
    finally:
        core.collect, core.add_request = real_collect, real_add
        await client.close()
        await engine.close()


async def test_tracing_off_takes_no_stamp_and_keeps_the_tokens():
    texts, stamps = {}, {}
    for tracing in (True, False):
        engine, client = await _engine_client(tracing=tracing)
        core = engine.engine
        try:
            real_add = core.add_request

            def spy_add(request_id, _real=real_add, _t=tracing, **kwargs):
                stamps[_t] = (kwargs["arrival_time"], kwargs["submitted_time"])
                _real(request_id, **kwargs)

            core.add_request = spy_add
            status, texts[tracing] = await _stream(
                client, "gate", {"x-request-start": make_request_start(
                    time.time())}, max_tokens=6)
            assert status == 200
            counts = sum(h.count for h in core.obs.request_hists.values())
            assert (counts > 0) is tracing
        finally:
            await client.close()
            await engine.close()
    assert texts[True] == texts[False] and texts[True]
    assert stamps[False] == (None, None)
    assert None not in stamps[True] and stamps[True][0] <= stamps[True][1]


# -- the router ------------------------------------------------------------------


async def test_router_strips_client_request_start_and_stamps_its_own():
    from tests.test_router_e2e import start_fake_engine, start_router

    state, engine = await start_fake_engine(ttft=0.01, tokens_per_sec=500.0)
    try:
        app, server, client = await start_router(
            [str(engine.make_url("")).rstrip("/")], ["fake/llama-3-8b"])
        try:
            t0 = time.time()
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "hello",
                      "max_tokens": 3, "stream": True},
                headers={"x-request-id": "rs-1", "X-Request-Start": "t=1.0"})
            await resp.read()
            t1 = time.time()
            assert resp.status == 200
            seen = [v for k, v in state.last_headers.items()
                    if k.lower() == "x-request-start"]
            assert len(seen) == 1 and seen[0] != "t=1.0"
            stamped = parse_request_start(seen[0], t1)
            assert stamped is not None and t0 <= stamped <= t1
            # The engine timed the hop, under the router's trace and span.
            assert state.obs.request_hists["request_upstream"].count == 1
            assert 0 <= state.obs.request_hists["request_upstream"].sum < 1
            joined = await (await client.get("/debug/requests/rs-1")).json()
            assert joined["engine"]["trace_id"] == joined["trace_id"]
            assert (joined["engine"]["attrs"]["parent_span_id"]
                    == joined["router"]["attrs"]["span_id"])
            upstream = [s for s in joined["engine"]["spans"]
                        if s["name"] == "engine.upstream"]
            assert len(upstream) == 1 and upstream[0]["start"] == stamped
        finally:
            await client.close()
    finally:
        await engine.close()
