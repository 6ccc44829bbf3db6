"""Overload protection + graceful lifecycle (docs/robustness.md), driven
end to end through the fake engine's deterministic fault-injection
surface and the real CPU tiny-llama engine — no TPU, no flaky network:

* circuit breaker state machine (open / half-open probe / close,
  exponential windows, 429-as-backpressure-never-failure),
* bounded admission under 2x oversubscription (structured 429s, flat
  admitted ITL, queue-depth bound),
* deadline propagation (router shed, engine admission shed, queued-expiry
  sweep aborting waiting sequences),
* drain (POST /drain: readiness flips, new work 503 + Connection: close,
  in-flight streams finish, exit callback fires inside the grace),
* step-loop watchdog failing /health liveness,
* the stalled-stream idle-read teardown and the router->engine
  disconnect-abort path,
* default-off-safe gates (--no-admission-control / --no-circuit-breaker
  parity).
"""

import asyncio
import heapq
import itertools
import json
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.router.circuit_breaker import CircuitBreaker
from production_stack_tpu.testing.fake_engine import (
    FakeEngineState,
    build_fake_engine_app,
)
from production_stack_tpu.utils.drain import DRAIN_CONTROLLER, DrainController

from tests.test_router_e2e import start_fake_engine, start_router

pytestmark = pytest.mark.chaos


async def start_fake(**kwargs):
    state = FakeEngineState(**kwargs)
    server = TestServer(build_fake_engine_app(state))
    await server.start_server()
    return state, server


def url_of(server) -> str:
    return str(server.make_url("")).rstrip("/")


async def sse_events(resp, now=time.monotonic, on_event=None):
    """(timestamp, payload) for each SSE data event of a streamed body."""
    events = []
    buf = b""
    async for chunk in resp.content.iter_any():
        buf += chunk
        while b"\n\n" in buf:
            frame, buf = buf.split(b"\n\n", 1)
            if frame.startswith(b"data: "):
                events.append((now(), frame[len(b"data: "):]))
                if on_event is not None:
                    on_event(events[-1][1])
    return events


def itl_p95(token_times):
    gaps = sorted(b - a for a, b in zip(token_times, token_times[1:]))
    assert gaps, "need at least two tokens for an ITL sample"
    return gaps[int(0.95 * (len(gaps) - 1))]


class StreamClock:
    """The time one fake engine's service runs on, for a test that measures
    token gaps: only the engine's own sleeps move it.  A sleeper wakes when
    the clock reaches its hour, and the clock goes to the earliest sleeper's
    hour once nothing else can happen before it: every request a client has
    open sleeps in the engine, and every token the engine has written has
    been read.  A chunk's stamp is then the hour it was written at, however
    long the machine took to carry it, and a chunk the engine held back
    (or a router buffered) stops the clock instead of passing unseen."""

    def __init__(self, state):
        self.state = state
        self.state.sleep = self.sleep
        self.time = 0.0
        self.open = 0   # requests sent whose answer is not read to its end
        self.read = 0   # token chunks the clients have read
        self._sleepers = []  # heap of (hour, nth, future)
        self._nth = itertools.count()

    def now(self):
        return self.time

    async def sleep(self, delay):
        woken = asyncio.get_running_loop().create_future()
        heapq.heappush(
            self._sleepers, (self.time + delay, next(self._nth), woken))
        await woken

    def on_event(self, payload):
        if payload != b"[DONE]" and (
            json.loads(payload)["choices"][0]["finish_reason"] is None
        ):
            self.read += 1

    async def run(self, work, real_limit_s=60.0):
        """``work``'s result, the clock moved for it as long as it runs."""
        task = asyncio.ensure_future(work)
        deadline = time.monotonic() + real_limit_s
        while not task.done():
            if (
                self._sleepers
                and len(self._sleepers) == self.open
                and self.read == self.state.total_generated_tokens
            ):
                self.time, _, woken = heapq.heappop(self._sleepers)
                woken.set_result(None)
            elif time.monotonic() > deadline:
                task.cancel()
                raise AssertionError(
                    f"the stream stopped at {self.time:.3f}: {self.open} open, "
                    f"{len(self._sleepers)} asleep, {self.read} chunks read of "
                    f"{self.state.total_generated_tokens} written"
                )
            await asyncio.sleep(0)
        return task.result()


# -- circuit breaker state machine ------------------------------------------


def test_breaker_opens_after_consecutive_failures_and_probes_half_open():
    clock = [1000.0]
    br = CircuitBreaker(
        failure_threshold=5, open_base_s=2.0, open_max_s=60.0,
        clock=lambda: clock[0],
    )
    url = "http://e1"
    for _ in range(4):
        br.on_failure(url)
    assert br.available(url) and br.state_value(url) == 0
    br.on_failure(url)  # 5th consecutive -> open
    assert br.state_value(url) == 2
    assert not br.available(url)
    assert not br.on_attempt(url)
    # Window expires -> exactly ONE half-open probe.
    clock[0] += 2.01
    assert br.available(url)
    assert br.on_attempt(url)
    assert br.state_value(url) == 1
    assert not br.on_attempt(url)  # probe slot consumed
    # Probe fails -> re-open with DOUBLED window (exponential backoff).
    br.on_failure(url)
    assert br.state_value(url) == 2
    clock[0] += 2.01
    assert not br.available(url), "second window must be ~4s, not 2s"
    clock[0] += 2.0
    assert br.on_attempt(url)
    # Probe succeeds -> closed, failure count reset.
    br.on_success(url)
    assert br.state_value(url) == 0
    br.on_failure(url)
    assert br.available(url), "one failure after close must not re-open"


def test_breaker_429_is_backpressure_never_opens():
    clock = [0.0]
    br = CircuitBreaker(failure_threshold=3, clock=lambda: clock[0])
    url = "http://e1"
    br.on_failure(url)
    br.on_failure(url)  # one more would open
    for _ in range(50):
        br.on_backpressure(url, retry_after_s=2.0)
    assert br.state_value(url) == 0, "429s must never open the breaker"
    assert br.is_backpressured(url)
    # The 429 also proved reachability: the failure streak was reset.
    br.on_failure(url)
    assert br.state_value(url) == 0
    clock[0] += 2.1
    assert not br.is_backpressured(url)


# -- circuit breaker through the router -------------------------------------


async def test_breaker_e2e_open_no_traffic_then_half_open_recovery():
    s_bad, e_bad = await start_fake()
    s_ok, e_ok = await start_fake()
    try:
        app, server, client = await start_router(
            [url_of(e_bad), url_of(e_ok)],
            ["fake/llama-3-8b", "fake/llama-3-8b"],
            extra_args=["--breaker-failure-threshold", "5",
                        "--breaker-open-s", "0.4"],
        )
        try:
            from production_stack_tpu.router.services.request_service.request import (
                CIRCUIT_BREAKER,
            )

            # The open window runs on a clock the test moves: on a busy
            # machine four requests can take longer than 0.4 s of wall
            # time, and the half-open probe then lands among them.
            breaker = app["registry"].get(CIRCUIT_BREAKER)
            now = [1000.0]
            breaker._clock = lambda: now[0]
            s_bad.inject("refuse", count=-1)
            body = {"model": "fake/llama-3-8b", "prompt": "x",
                    "max_tokens": 2}
            # Every request succeeds via failover while the breaker counts
            # the bad backend's consecutive connect failures up to 5
            # (round-robin routes only every other request there first,
            # so 12 requests guarantee >= 5 connect failures).
            for _ in range(12):
                resp = await client.post("/v1/completions", json=body)
                assert resp.status == 200, await resp.text()
            assert breaker.state_value(url_of(e_bad)) == 2  # open
            # Open: the bad backend receives NO traffic at all.
            hits_while_open = s_bad.data_plane_hits
            for _ in range(4):
                resp = await client.post("/v1/completions", json=body)
                assert resp.status == 200
            assert s_bad.data_plane_hits == hits_while_open
            # Heal the backend, wait out the open window: the next
            # requests include ONE half-open probe that closes the
            # breaker, after which traffic resumes.
            s_bad.clear_injection("refuse")
            now[0] += 0.45
            for _ in range(4):
                resp = await client.post("/v1/completions", json=body)
                assert resp.status == 200
            assert breaker.state_value(url_of(e_bad)) == 0
            assert s_bad.data_plane_hits > hits_while_open
            # Router /metrics exports the state gauge.
            text = await (await client.get("/metrics")).text()
            assert "tpu_router:circuit_state" in text
        finally:
            await client.close()
    finally:
        await e_bad.close()
        await e_ok.close()


async def test_engine_429_sheds_weight_but_never_opens_breaker():
    s_busy, e_busy = await start_fake()
    s_ok, e_ok = await start_fake()
    try:
        app, server, client = await start_router(
            [url_of(e_busy), url_of(e_ok)],
            ["fake/llama-3-8b", "fake/llama-3-8b"],
            extra_args=["--breaker-failure-threshold", "3"],
        )
        try:
            s_busy.inject("reject_429", count=-1, retry_after=5)
            body = {"model": "fake/llama-3-8b", "prompt": "x",
                    "max_tokens": 2}
            statuses = []
            for _ in range(10):
                resp = await client.post("/v1/completions", json=body)
                statuses.append(resp.status)
            from production_stack_tpu.router.services.request_service.request import (
                CIRCUIT_BREAKER,
            )

            breaker = app["registry"].get(CIRCUIT_BREAKER)
            # Backpressure, not failure: the breaker stays closed however
            # many 429s arrive...
            assert breaker.state_value(url_of(e_busy)) == 0
            assert breaker.is_backpressured(url_of(e_busy))
            # ...and after the first 429 the routing weight drop steers
            # everything to the relieved backend.
            assert statuses.count(200) >= 9
            assert s_ok.total_requests >= 9
        finally:
            await client.close()
    finally:
        await e_busy.close()
        await e_ok.close()


async def test_5xx_responses_open_breaker_via_injection():
    """Consecutive 5xx responses (not just connect failures) open the
    breaker; while open, the lone backend yields a structured 503
    circuit_open instead of hammering the failing engine."""
    state, engine = await start_fake()
    try:
        app, server, client = await start_router(
            [url_of(engine)], ["fake/llama-3-8b"],
            extra_args=["--breaker-failure-threshold", "3",
                        "--breaker-open-s", "30"],
        )
        try:
            state.inject("error_5xx", count=3, status=503)
            body = {"model": "fake/llama-3-8b", "prompt": "x",
                    "max_tokens": 2}
            for _ in range(3):
                resp = await client.post("/v1/completions", json=body)
                assert resp.status == 503  # proxied injected failure
            from production_stack_tpu.router.services.request_service.request import (
                CIRCUIT_BREAKER,
            )

            breaker = app["registry"].get(CIRCUIT_BREAKER)
            assert breaker.state_value(url_of(engine)) == 2
            hits = state.data_plane_hits
            resp = await client.post("/v1/completions", json=body)
            assert resp.status == 503
            assert (await resp.json())["error"]["type"] == "circuit_open"
            assert state.data_plane_hits == hits, "open backend got traffic"
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_slow_admission_injection_delays_first_byte():
    state, server = await start_fake(ttft=0.0, tokens_per_sec=1000.0)
    client = TestClient(server)
    await client.start_server()
    try:
        state.inject("slow_admission", delay_s=0.25)
        t0 = time.monotonic()
        resp = await client.post(
            "/v1/completions",
            json={"model": state.model, "prompt": "x", "max_tokens": 1},
        )
        await resp.read()
        assert time.monotonic() - t0 >= 0.25
    finally:
        await client.close()


# -- bounded admission under oversubscription -------------------------------


async def test_oversubscription_shedding_bounds_itl():
    """2x oversubscription against a capacity-modeled fake engine: with
    bounded admission ON the excess sheds as structured 429s and the
    ADMITTED requests' p95 ITL stays within 1.5x the unloaded baseline;
    with admission OFF everyone is admitted and everyone degrades.

    The gaps are those of the delivered stream, each chunk stamped as the
    client reads it, on the engine's own clock (``StreamClock``): under six
    busy workers a 10 ms sleep on the wall clock is as long as the machine
    makes it, and the ratio read the machine."""
    capacity, n_load, n_tokens = 4, 8, 30

    async def run(admission: bool):
        state, server = await start_fake(
            capacity=capacity, max_queued=0, admission_control=admission,
            tokens_per_sec=100.0, ttft=0.005,
        )
        clock = StreamClock(state)
        client = TestClient(server)
        await client.start_server()
        body = {"model": state.model, "prompt": "x", "stream": True,
                "max_tokens": n_tokens}

        async def one():
            clock.open += 1
            try:
                resp = await client.post("/v1/completions", json=body)
                if resp.status != 200:
                    detail = json.loads(await resp.text())
                    return ("rejected", resp, detail)
                events = await sse_events(resp, clock.now, clock.on_event)
            finally:
                clock.open -= 1
            times = [t for t, payload in events if payload != b"[DONE]"]
            return ("admitted", resp, times)

        # Unloaded baseline: one stream alone.
        _, _, baseline_times = await clock.run(one())
        baseline = itl_p95(baseline_times)
        # 2x capacity, simultaneously.
        results = await clock.run(
            asyncio.gather(*[one() for _ in range(n_load)]))
        admitted = [r for r in results if r[0] == "admitted"]
        rejected = [r for r in results if r[0] == "rejected"]
        await client.close()
        return state, baseline, admitted, rejected

    state, baseline, admitted, rejected = await run(admission=True)
    # The excess shed with structured 429s + Retry-After...
    assert len(admitted) == capacity
    assert len(rejected) == n_load - capacity
    for _, resp, detail in rejected:
        assert resp.status == 429
        assert detail["error"]["type"] == "overloaded"
        assert int(resp.headers["Retry-After"]) >= 1
        assert "kv_usage_perc" in detail["error"]["detail"]
    # ...the counter agrees (no unbounded growth)...
    assert state.admission_rejected == n_load - capacity
    # ...and the admitted requests' tail ITL stayed flat.
    shed_p95 = max(itl_p95(times) for _, _, times in admitted)
    assert shed_p95 <= 1.5 * baseline, (
        f"admitted p95 ITL {shed_p95 * 1e3:.1f}ms exceeded 1.5x baseline "
        f"{baseline * 1e3:.1f}ms under shed load"
    )

    # Without admission control everyone is admitted — and the
    # oversubscribed batch degrades everyone (the legacy failure mode).
    state2, baseline2, admitted2, rejected2 = await run(admission=False)
    assert not rejected2 and len(admitted2) == n_load
    assert state2.admission_rejected == 0
    noshed_p95 = max(itl_p95(times) for _, _, times in admitted2)
    assert noshed_p95 > shed_p95, (
        "unbounded admission should degrade ITL beyond the shedding run"
    )


async def test_fake_engine_queue_depth_gauge_bounded_under_shed():
    state, server = await start_fake(
        capacity=2, max_queued=1, admission_control=True,
        tokens_per_sec=50.0, ttft=0.0,
    )
    client = TestClient(server)
    await client.start_server()
    try:
        body = {"model": state.model, "prompt": "x", "stream": True,
                "max_tokens": 10}
        tasks = [
            asyncio.create_task(client.post("/v1/completions", json=body))
            for _ in range(6)
        ]
        await asyncio.sleep(0.05)
        text = await (await client.get("/metrics")).text()
        waiting = [
            float(line.split()[-1]) for line in text.splitlines()
            if line.startswith("tpu:num_requests_waiting")
        ][0]
        assert waiting <= state.max_queued, (
            f"queue depth {waiting} exceeded max_queued={state.max_queued}"
        )
        assert "tpu:admission_rejected_total" in text
        for t in tasks:
            resp = await t
            await resp.read()
    finally:
        await client.close()


async def test_fake_engine_gauges_count_a_request_once():
    """Running and waiting are disjoint, as the real engine's scheduler
    reports them: the router adds the two into one load, and a request
    counted in both made a stale scrape outweigh the router's own count."""
    from production_stack_tpu.router.stats.engine_stats import EngineStats

    state, server = await start_fake(capacity=2, max_queued=8)
    client = TestClient(server)
    await client.start_server()
    try:
        for in_flight, (running, waiting) in {0: (0, 0), 2: (2, 0), 6: (2, 4)}.items():
            state.num_running = in_flight
            stats = EngineStats.from_prometheus_text(
                await (await client.get("/metrics")).text())
            assert (stats.num_running_requests, stats.num_queuing_requests) == (
                running, waiting)
    finally:
        state.num_running = 0
        await client.close()


# -- deadline propagation ----------------------------------------------------


async def test_router_sheds_expired_deadline_without_touching_backend():
    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [url_of(engine)], ["fake/llama-3-8b"]
        )
        try:
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 2},
                headers={"X-Request-Deadline": repr(time.time() - 5)},
            )
            assert resp.status == 504
            body = await resp.json()
            assert body["error"]["type"] == "deadline_expired"
            assert state.total_requests == 0, "expired request was forwarded"

            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 2},
                headers={"X-Request-Deadline": "not-a-number"},
            )
            assert resp.status == 400

            # Router /metrics carries the shed counter.
            text = await (await client.get("/metrics")).text()
            assert "tpu_router:deadline_expired_total" in text
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_router_propagates_timeout_body_field_as_absolute_header():
    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [url_of(engine)], ["fake/llama-3-8b"]
        )
        try:
            t0 = time.time()
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 2, "timeout": 30},
            )
            assert resp.status == 200
            fwd = state.last_headers.get("x-request-deadline")
            assert fwd is not None, "deadline header not propagated"
            assert t0 + 25 < float(fwd) < t0 + 40
        finally:
            await client.close()
    finally:
        await engine.close()


def _tiny_async_engine(**sched_overrides):
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    cfg = EngineConfig(
        model=ModelConfig(),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=sched_overrides.pop("max_num_seqs", 4),
            prefill_buckets=(16, 32, 64),
            max_model_len=512,
            **sched_overrides,
        ),
    )
    return AsyncEngine(cfg)


async def _start_engine_app(engine, **kwargs):
    from production_stack_tpu.engine.server.api_server import build_engine_app

    app = build_engine_app(engine, served_model="tiny-llama", **kwargs)
    server = TestServer(app)
    await server.start_server()
    client = TestClient(server)
    return app, server, client


async def test_engine_sheds_expired_deadline_at_admission():
    engine = _tiny_async_engine()
    app, server, client = await _start_engine_app(engine)
    try:
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "hi", "max_tokens": 4},
            headers={"X-Request-Deadline": repr(time.time() - 1)},
        )
        assert resp.status == 504
        assert (await resp.json())["error"]["type"] == "deadline_expired"
        text = await (await client.get("/metrics")).text()
        assert "tpu:deadline_expired_total 1.0" in text
    finally:
        await client.close()


async def test_engine_aborts_queued_sequence_whose_deadline_expires():
    """max_num_seqs=1: a long-running stream holds the only batch slot;
    the second request's deadline expires while it WAITS, and the
    scheduler-pass sweep aborts it (504) instead of leaving it occupying
    queue and (eventually) KV blocks."""
    engine = _tiny_async_engine(max_num_seqs=1)
    app, server, client = await _start_engine_app(engine)
    try:
        long_resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "stream on",
                  "max_tokens": 400, "ignore_eos": True, "stream": True},
        )
        assert long_resp.status == 200
        # Ensure the long request occupies the slot before r2 arrives.
        await long_resp.content.readany()
        t0 = time.time()
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "queued behind",
                  "max_tokens": 4},
            headers={"X-Request-Deadline": repr(time.time() + 0.3)},
        )
        assert resp.status == 504, await resp.text()
        assert (await resp.json())["error"]["type"] == "deadline_expired"
        assert time.time() - t0 < 10
        # The expired sequence left the queue entirely.
        assert engine.engine.scheduler.num_waiting == 0
        text = await (await client.get("/metrics")).text()
        assert "tpu:deadline_expired_total 1.0" in text
        long_resp.close()
    finally:
        await client.close()


# -- bounded admission on the real engine ------------------------------------


async def test_real_engine_admission_cap_and_parity_gate():
    engine = _tiny_async_engine(max_num_seqs=1, max_queued_requests=1)
    app, server, client = await _start_engine_app(engine)
    try:
        # Fill the batch slot + the one queue slot with streams.
        running = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "a", "max_tokens": 300,
                  "ignore_eos": True, "stream": True},
        )
        assert running.status == 200
        await running.content.readany()
        queued_task = asyncio.create_task(client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "b", "max_tokens": 4},
        ))
        # Give the queued request time to submit.
        for _ in range(100):
            await asyncio.sleep(0.01)
            if engine.engine.scheduler.num_waiting >= 1:
                break
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "c", "max_tokens": 4},
        )
        assert resp.status == 429, await resp.text()
        body = await resp.json()
        assert body["error"]["type"] == "overloaded"
        assert body["error"]["detail"]["max_queued_requests"] == 1
        assert int(resp.headers["Retry-After"]) >= 1
        text = await (await client.get("/metrics")).text()
        assert "tpu:admission_rejected_total 1.0" in text
        assert "tpu:queued_prompt_tokens" in text
        running.close()
        resp2 = await queued_task
        assert resp2.status == 200
    finally:
        await client.close()

    # Parity gate: --no-admission-control (admission_control=False)
    # admits unboundedly — check_admission never rejects.
    engine2 = _tiny_async_engine(
        max_num_seqs=1, max_queued_requests=1, admission_control=False
    )
    assert engine2.check_admission(10_000, 10_000_000) is None


def test_admission_config_resolution_and_validation():
    from production_stack_tpu.engine.config import (
        SchedulerConfig,
        config_from_preset,
    )

    cfg = SchedulerConfig(max_num_seqs=8, max_model_len=2048)
    assert cfg.admission_enabled
    assert cfg.queued_requests_cap == 32
    assert cfg.queued_tokens_cap == 2 * 8 * 2048
    off = config_from_preset(
        "tiny-llama", **{"scheduler.admission_control": False}
    )
    assert not off.scheduler.admission_enabled
    with pytest.raises(ValueError):
        SchedulerConfig(max_queued_requests=0)
    with pytest.raises(ValueError):
        SchedulerConfig(step_watchdog_s=-1)


# -- drain -------------------------------------------------------------------


async def test_engine_drain_completes_streams_rejects_new_work():
    engine = _tiny_async_engine()
    app, server, client = await _start_engine_app(engine, drain_grace_s=10.0)
    exits = []
    app["drain"].exit_cb = lambda: exits.append(True)
    try:
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "drain me",
                  "max_tokens": 40, "ignore_eos": True, "stream": True},
        )
        assert resp.status == 200
        await resp.content.readany()  # stream is live
        d = await client.post("/drain")
        assert (await d.json())["draining"] is True
        # Readiness flips; liveness keeps passing (kubelet must not kill
        # the pod mid-stream).
        assert (await client.get("/ready")).status == 503
        assert (await client.get("/health")).status == 200
        # New admissions: 503 + Connection: close.
        rej = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "late", "max_tokens": 2},
        )
        assert rej.status == 503
        assert (await rej.json())["error"]["type"] == "shutting_down"
        assert rej.headers.get("Connection", "").lower() == "close"
        # The admitted stream runs to completion.
        raw = await resp.read()
        assert raw.strip().endswith(b"data: [DONE]")
        # Drain finishes inside the grace and fires the exit callback
        # (in production: SIGINT-to-self -> aiohttp graceful exit -> 0).
        assert await app["drain"].wait(timeout=10) is True
        assert exits == [True]
        # POST /drain is idempotent (preStop then SIGTERM converge).
        assert (await client.post("/drain")).status == 200
    finally:
        await client.close()


async def test_router_drain_completes_streams_rejects_new_work():
    state, engine = await start_fake_engine(tokens_per_sec=100.0)
    try:
        app, server, client = await start_router(
            [url_of(engine)], ["fake/llama-3-8b"]
        )
        drain = app["registry"].get(DRAIN_CONTROLLER)
        exits = []
        drain.exit_cb = lambda: exits.append(True)
        try:
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "stream": True, "max_tokens": 30},
            )
            assert resp.status == 200
            await resp.content.readany()
            d = await client.post("/drain")
            assert (await d.json())["draining"] is True
            assert (await client.get("/ready")).status == 503
            assert (await client.get("/health")).status == 200
            rej = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "y",
                      "max_tokens": 2},
            )
            assert rej.status == 503
            assert (await rej.json())["error"]["type"] == "shutting_down"
            assert rej.headers.get("Connection", "").lower() == "close"
            raw = await resp.read()
            assert raw.strip().endswith(b"data: [DONE]")
            assert await drain.wait(timeout=10) is True
            assert exits == [True]
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_drain_grace_expiry_exits_anyway():
    drain = DrainController(grace_s=0.15, busy_fn=lambda: True)
    exits = []
    drain.exit_cb = lambda: exits.append(True)
    drain.begin()
    assert await drain.wait(timeout=5) is False  # grace expired while busy
    assert exits == [True]


async def test_engine_drain_gates_all_data_plane_endpoints():
    """The drain gate is a middleware: /tokenize (and every other POST
    data-plane path) must 503 during a drain, not just completions."""
    engine = _tiny_async_engine()
    app, server, client = await _start_engine_app(engine)
    try:
        assert (await client.post(
            "/tokenize", json={"prompt": "hi"}
        )).status == 200
        await client.post("/drain")
        for path, payload in [
            ("/tokenize", {"prompt": "hi"}),
            ("/detokenize", {"tokens": [1]}),
            ("/v1/embeddings", {"input": "x"}),
            ("/score", {"text_1": "a", "text_2": "b"}),
        ]:
            resp = await client.post(path, json=payload)
            assert resp.status == 503, (path, resp.status)
            assert (await resp.json())["error"]["type"] == "shutting_down"
            assert resp.headers.get("Connection", "").lower() == "close"
        # Control plane stays served.
        assert (await client.get("/metrics")).status == 200
        assert (await client.post("/drain")).status == 200
    finally:
        await client.close()


async def test_idle_timeout_before_headers_sheds_504_without_replay():
    """A backend that accepted the request but produced no response bytes
    within --stream-idle-timeout-s is shed with a 504 — NOT replayed on a
    fallback (that would duplicate the whole generation) and NOT counted
    as a circuit-breaker failure (it is alive, just slow)."""
    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [url_of(engine)], ["fake/llama-3-8b"],
            extra_args=["--stream-idle-timeout-s", "0.3"],
        )
        try:
            state.inject("slow_admission", delay_s=5.0, count=1)
            t0 = time.monotonic()
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 2},
            )
            assert resp.status == 504, await resp.text()
            assert (await resp.json())["error"]["type"] == "backend_timeout"
            assert time.monotonic() - t0 < 3
            assert state.data_plane_hits == 1, "request was replayed"
            from production_stack_tpu.router.services.request_service.request import (
                CIRCUIT_BREAKER,
            )

            breaker = app["registry"].get(CIRCUIT_BREAKER)
            assert breaker.state_value(url_of(engine)) == 0
            # The backend recovers; the next request is served normally.
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 2},
            )
            assert resp.status == 200
        finally:
            await client.close()
    finally:
        await engine.close()


# -- step-loop watchdog ------------------------------------------------------


async def test_watchdog_fails_liveness_when_step_loop_stalls():
    engine = _tiny_async_engine()
    app, server, client = await _start_engine_app(engine)
    try:
        # Healthy: the loop stamps every iteration.
        for _ in range(100):
            await asyncio.sleep(0.01)
            if engine._last_step_ts is not None:
                break
        health = await client.get("/health")
        assert health.status == 200
        assert (await health.json())["last_step_age_s"] < 5
        text = await (await client.get("/metrics")).text()
        assert "tpu:last_step_age_seconds" in text
        # Stall the loop (clean thread exit leaves the stamp frozen —
        # exactly what a hung device dispatch looks like to the probe).
        engine._shutdown.set()
        engine._wakeup.set()
        engine._thread.join(timeout=10)
        engine.engine.config.scheduler.step_watchdog_s = 0.05
        await asyncio.sleep(0.15)
        health = await client.get("/health")
        assert health.status == 503
        assert "stalled" in (await health.json())["problem"]
        assert (await client.get("/ready")).status == 503
    finally:
        await client.close()


# -- stalled streams + disconnect-abort propagation --------------------------


async def test_stalled_stream_torn_down_and_abort_propagates():
    """A backend stream that goes byte-less past --stream-idle-timeout-s
    is torn down by the router; the teardown cancels the engine-side
    handler (the abort path), so the stall cannot leak forever."""
    state, engine = await start_fake_engine(tokens_per_sec=200.0)
    try:
        app, server, client = await start_router(
            [url_of(engine)], ["fake/llama-3-8b"],
            extra_args=["--stream-idle-timeout-s", "0.3"],
        )
        try:
            state.inject("stall_stream", after_tokens=2)
            t0 = time.monotonic()
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "stream": True, "max_tokens": 50},
            )
            assert resp.status == 200
            with pytest.raises(Exception):
                # The relay dies when sock_read trips; reading the body
                # surfaces it as a connection/payload error.
                while True:
                    chunk = await resp.content.readany()
                    if not chunk:
                        raise ConnectionError("stream ended early")
            assert time.monotonic() - t0 < 5, "stall was not torn down"
            # Abort propagated to the engine: its handler was cancelled.
            for _ in range(100):
                if state.aborted_requests:
                    break
                await asyncio.sleep(0.02)
            assert state.aborted_requests, "engine never saw the abort"
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_client_disconnect_mid_stream_releases_engine_state():
    """Router->engine abort path end to end on the REAL engine: a client
    that vanishes mid-stream must release the engine-side sequence (and
    its KV blocks) within a step, not leave it decoding for nobody."""
    from production_stack_tpu.engine.server.api_server import build_engine_app

    engine = _tiny_async_engine()
    eng_server = TestServer(build_engine_app(engine, served_model="tiny-llama"))
    await eng_server.start_server()
    try:
        app, server, client = await start_router(
            [str(eng_server.make_url("")).rstrip("/")], ["tiny-llama"]
        )
        try:
            resp = await client.post(
                "/v1/completions",
                json={"model": "tiny-llama", "prompt": "leak check",
                      "max_tokens": 400, "ignore_eos": True,
                      "stream": True},
            )
            assert resp.status == 200
            await resp.content.readany()
            assert engine.engine.scheduler.num_running == 1
            pool_in_use = engine.engine.block_pool.usage
            assert pool_in_use > 0
            # Client walks away mid-stream.
            resp.close()
            for _ in range(250):
                if (
                    engine.engine.scheduler.num_running == 0
                    and not engine.engine.has_unfinished()
                ):
                    break
                await asyncio.sleep(0.02)
            assert engine.engine.scheduler.num_running == 0
            assert not engine.engine.has_unfinished()
            assert not engine._queues, "event queue leaked"
        finally:
            await client.close()
    finally:
        await eng_server.close()


# -- default-off-safe gates --------------------------------------------------


async def test_no_circuit_breaker_flag_reproduces_legacy_path():
    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [url_of(engine), "http://127.0.0.1:1"],
            ["fake/llama-3-8b", "fake/llama-3-8b"],
            extra_args=["--no-circuit-breaker"],
        )
        from production_stack_tpu.router.services.request_service.request import (
            CIRCUIT_BREAKER,
        )

        assert app["registry"].get(CIRCUIT_BREAKER) is None
        try:
            # Failover keeps working exactly as before the breaker.
            for _ in range(6):
                resp = await client.post(
                    "/v1/completions",
                    json={"model": "fake/llama-3-8b", "prompt": "x",
                          "max_tokens": 1},
                )
                assert resp.status == 200
        finally:
            await client.close()
    finally:
        await engine.close()


# -- registry close grace (satellite) ----------------------------------------


async def test_registry_close_waits_bounded_grace():
    from production_stack_tpu.utils.registry import ServiceRegistry

    closed = []

    class Fast:
        async def close(self):
            closed.append("fast")

    class SyncSvc:
        def close(self):
            closed.append("sync")

    class Hung:
        async def close(self):
            await asyncio.sleep(30)
            closed.append("hung")

    class Broken:
        def close(self):
            raise RuntimeError("boom")

    registry = ServiceRegistry()
    registry.set("fast", Fast())
    registry.set("hung", Hung())
    registry.set("sync", SyncSvc())
    registry.set("broken", Broken())
    registry.set("plain", object())  # no close(): skipped
    t0 = time.monotonic()
    await registry.close(grace_s=0.3)
    elapsed = time.monotonic() - t0
    assert elapsed < 5, "close() must be bounded by the grace"
    assert "fast" in closed and "sync" in closed
    assert "hung" not in closed  # timed out, skipped, logged
    assert not registry.contains("fast") and not registry.contains("plain")
