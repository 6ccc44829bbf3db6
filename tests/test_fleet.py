"""Fleet-level admission control + the chaos-tested 2→N→2 guarantee
(ROADMAP item 2; docs/robustness.md "Fleet admission & autoscaling
contract"):

* capacity-model units: optimistic prior, clamp-on-evidence (queueing /
  SLO breach / engine 429), probe-up recovery, zero-headroom windows,
  per-role pools, priority degradation ladder, pod-churn pruning;
* router e2e: fleet sheds are structured 429s (type ``fleet_overloaded``
  + Retry-After) counted under
  tpu_router:fleet_admission_rejected_total{reason} with headroom/score
  gauges on /metrics, --no-fleet-admission parity;
* a 429-storm from one backend redistributes load WITHOUT opening its
  breaker and feeds the capacity model a zero-headroom observation;
* the acceptance chaos replay: 20 fake engines, seeded 10x diurnal QPS
  swing, replicas scaled 2→N→2 through the drain path mid-replay with
  injected kill/stall/429-storm — zero dropped in-flight streams outside
  the stall fault, goodput >= 90% of the capacity-model-perfect oracle,
  and every engine-side 429 preceded by router-side fleet sheds in the
  same overload window.
"""

import asyncio
import json

import pytest

from production_stack_tpu.router.capacity import (
    CapacityModel,
    FleetAdmission,
    request_priority,
)
from production_stack_tpu.router.service_discovery import EndpointInfo
from production_stack_tpu.router.stats.engine_stats import EngineStats
from production_stack_tpu.router.stats.request_stats import RequestStats
from production_stack_tpu.testing.fleet import FleetHarness

from tests.test_router_e2e import start_fake_engine, start_router

pytestmark = pytest.mark.chaos


def eps(*urls, roles=None):
    return [
        EndpointInfo(url=u, model_names=["m"], role=(roles[i] if roles else None))
        for i, u in enumerate(urls)
    ]


# -- capacity model units ----------------------------------------------------


def test_prior_is_optimistic_and_clamps_on_queue_evidence():
    clock = [100.0]
    m = CapacityModel(default_slots=64, clock=lambda: clock[0])
    url = "http://e1"
    # No evidence: full prior headroom, score 1.
    assert m.backend_headroom(url) == 64
    assert m.capacity_score(url) == 1.0
    # Engine-side queueing observed at concurrency 12: the backend is at
    # capacity there — slots clamp down to 12.
    m.observe(url, inflight=12, queued_requests=3)
    assert m.slots_of(url) == 12
    assert m.backend_headroom(url, inflight=12) == 0
    # Healthy readings at the frontier probe back up one step at a time.
    m.observe(url, inflight=12, queued_requests=0)
    assert m.slots_of(url) == 13


def test_slo_breach_clamps_and_qps_knee_tracks():
    m = CapacityModel(default_slots=64, slo_p95_itl_s=0.5, clock=lambda: 0.0)
    url = "http://e1"
    # Healthy at 30 QPS: the knee tracks the best healthy throughput.
    m.observe(url, inflight=4, qps=30.0, p95_itl=0.2)
    assert m.qps_capacity_of(url) == 30.0
    # p95 ITL breaches the SLO at concurrency 9: slots clamp to 9, the
    # QPS knee shrinks proportionally to the breach.
    m.observe(url, inflight=9, qps=40.0, p95_itl=1.0)
    assert m.slots_of(url) == 9
    assert m.qps_capacity_of(url) == pytest.approx(20.0)


def test_backpressure_is_zero_headroom_for_retry_after_window():
    clock = [50.0]
    m = CapacityModel(default_slots=16, clock=lambda: clock[0])
    url = "http://e1"
    m.observe(url, inflight=10)
    m.on_backpressure(url, retry_after_s=2.0)
    assert m.slots_of(url) == 10  # clamped to the observed concurrency
    assert m.capacity_score(url) == 0.0
    assert m.backend_headroom(url, inflight=0) == 0.0  # saturated window
    clock[0] += 2.1
    assert m.backend_headroom(url, inflight=0) == 10.0  # window expired


def test_prune_drops_departed_backends():
    m = CapacityModel(clock=lambda: 0.0)
    m.observe("http://a", inflight=1)
    m.observe("http://b", inflight=1)
    gone = m.prune(["http://b"])
    assert gone == ["http://a"]
    assert "http://a" not in m.snapshot() and "http://b" in m.snapshot()


def test_admission_pools_are_role_aware():
    """A saturated prefill pool must NOT shed work the decode/fused pool
    could absorb; a saturated decode pool must."""
    clock = [0.0]
    m = CapacityModel(default_slots=4, clock=lambda: clock[0])
    adm = FleetAdmission(m, clock=lambda: clock[0])
    endpoints = eps(
        "http://pf", "http://dc", roles=["prefill", "decode"]
    )
    # Saturate ONLY the prefill backend.
    m.on_backpressure("http://pf", 5.0)
    stats = {
        "http://pf": RequestStats(uncompleted_requests=4),
        "http://dc": RequestStats(uncompleted_requests=0),
    }
    assert adm.check(endpoints, {}, stats) is None  # decode pool has room
    # Now saturate the decode backend too: shed, naming the decode pool.
    stats["http://dc"] = RequestStats(uncompleted_requests=4)
    shed = adm.check(endpoints, {}, stats)
    assert shed is not None and shed.reason == "no_headroom"
    assert shed.pool == "decode"


def test_priority_degradation_ladder():
    clock = [0.0]
    m = CapacityModel(default_slots=10, clock=lambda: clock[0])
    adm = FleetAdmission(
        m, low_priority_headroom_frac=0.3, clock=lambda: clock[0]
    )
    endpoints = eps("http://e1")
    # 8/10 slots used: headroom 2 < 30% of 10 — low-priority work sheds,
    # normal work does not.
    stats = {"http://e1": RequestStats(uncompleted_requests=8)}
    assert adm.check(endpoints, {}, stats, priority=0) is None
    shed = adm.check(endpoints, {}, stats, priority=1)
    assert shed is not None and shed.reason == "low_priority"
    # Headroom fully gone: everyone sheds.
    stats = {"http://e1": RequestStats(uncompleted_requests=10)}
    shed = adm.check(endpoints, {}, stats, priority=0)
    assert shed is not None and shed.reason == "no_headroom"


def test_engine_shed_counter_growth_is_saturation_evidence():
    """A growing tpu:admission_rejected_total between refreshes marks the
    backend saturated even when ANOTHER router absorbed the 429s."""
    clock = [0.0]
    m = CapacityModel(default_slots=32, refresh_interval_s=0.0,
                      clock=lambda: clock[0])
    endpoints = eps("http://e1")
    stats = {"http://e1": RequestStats(uncompleted_requests=6)}
    m.refresh(endpoints, {"http://e1": EngineStats(admission_rejected_total=5)},
              stats)
    assert m.capacity_score("http://e1") > 0  # first read seeds the counter
    m.refresh(endpoints, {"http://e1": EngineStats(admission_rejected_total=9)},
              stats)
    assert m.capacity_score("http://e1") == 0.0  # delta -> zero headroom
    assert m.slots_of("http://e1") == 6.0


def test_request_priority_parsing():
    assert request_priority({}, None) == 0
    assert request_priority({}, {"priority": 3}) == 3
    assert request_priority({"x-request-priority": "2"}, {"priority": 0}) == 2
    assert request_priority({}, {"priority": "junk"}) == 0


# -- router e2e --------------------------------------------------------------


async def _stream_until_stalled(client, model, max_tokens=500):
    """Start one long streaming request and return the response once the
    first chunk arrives (it then occupies a slot until closed)."""
    resp = await client.post(
        "/v1/chat/completions",
        json={"model": model, "stream": True, "max_tokens": max_tokens,
              "messages": [{"role": "user", "content": "hold a slot"}]},
    )
    assert resp.status == 200
    await resp.content.readany()
    return resp


async def test_fleet_shed_is_structured_429_with_metrics():
    state, engine = await start_fake_engine(tokens_per_sec=20.0)
    url = str(engine.make_url("")).rstrip("/")
    try:
        app, server, client = await start_router(
            [url], ["fake/llama-3-8b"],
            extra_args=["--fleet-default-slots", "1"],
        )
        try:
            holder = await _stream_until_stalled(client, "fake/llama-3-8b")
            # Slot occupied, prior = 1 -> fleet headroom exhausted.
            resp = await client.post(
                "/v1/chat/completions",
                json={"model": "fake/llama-3-8b", "stream": False,
                      "max_tokens": 2,
                      "messages": [{"role": "user", "content": "hi"}]},
            )
            assert resp.status == 429
            assert resp.headers.get("Retry-After")
            body = await resp.json()
            assert body["error"]["type"] == "fleet_overloaded"
            assert body["error"]["detail"]["reason"] == "no_headroom"
            # The shed never reached the engine: one data-plane hit only.
            assert state.data_plane_hits == 1
            holder.close()

            mresp = await client.get("/metrics")
            text = await mresp.text()
            assert (
                'tpu_router:fleet_admission_rejected_total{reason="no_headroom"} 1.0'
                in text
            )
            assert "tpu_router:fleet_headroom_slots" in text
            assert "tpu_router:backend_capacity_slots" in text
            assert "tpu_router:backend_capacity_score" in text
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_no_fleet_admission_flag_restores_legacy_path():
    state, engine = await start_fake_engine(tokens_per_sec=20.0)
    url = str(engine.make_url("")).rstrip("/")
    try:
        app, server, client = await start_router(
            [url], ["fake/llama-3-8b"],
            extra_args=["--fleet-default-slots", "1", "--no-fleet-admission"],
        )
        try:
            holder = await _stream_until_stalled(client, "fake/llama-3-8b")
            resp = await client.post(
                "/v1/chat/completions",
                json={"model": "fake/llama-3-8b", "stream": False,
                      "max_tokens": 2,
                      "messages": [{"role": "user", "content": "hi"}]},
            )
            # No fleet gate: the request reaches the engine and succeeds
            # (the fake has no capacity model configured here).
            assert resp.status == 200
            assert state.data_plane_hits == 2
            holder.close()
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_429_storm_redistributes_without_opening_breaker():
    """Satellite: one backend 429-storming loses routing weight AND
    registers as zero headroom in the capacity model, load redistributes,
    and its breaker stays closed throughout."""
    from production_stack_tpu.router.capacity import CAPACITY_MODEL
    from production_stack_tpu.router.services.request_service.request import (
        CIRCUIT_BREAKER,
    )

    s_storm, e_storm = await start_fake_engine(tokens_per_sec=2000.0)
    s_ok, e_ok = await start_fake_engine(tokens_per_sec=2000.0)
    url_storm = str(e_storm.make_url("")).rstrip("/")
    url_ok = str(e_ok.make_url("")).rstrip("/")
    try:
        app, server, client = await start_router(
            [url_storm, url_ok],
            ["fake/llama-3-8b", "fake/llama-3-8b"],
        )
        try:
            s_storm.inject("reject_429", retry_after=2, count=-1)
            statuses = []
            for _ in range(30):
                resp = await client.post(
                    "/v1/completions",
                    json={"model": "fake/llama-3-8b", "prompt": "x",
                          "max_tokens": 1},
                )
                statuses.append(resp.status)
                await resp.read()
            # The storm backend answered at most a couple of 429s before
            # losing routing weight; the healthy backend absorbed the rest.
            assert statuses.count(200) >= 27, statuses
            assert s_ok.total_requests >= 27
            breaker = app["registry"].get(CIRCUIT_BREAKER)
            assert breaker.state_value(url_storm) == 0, "429s must not open"
            capacity = app["registry"].get(CAPACITY_MODEL)
            assert capacity.capacity_score(url_storm) == 0.0
            assert capacity.capacity_score(url_ok) > 0.0
        finally:
            await client.close()
    finally:
        await e_storm.close()
        await e_ok.close()


async def test_fleet_shed_precedes_engine_429_once_learned():
    """Once the scrape teaches the model a backend's bound, the NEXT
    overload sheds at the router without the engine ever seeing it."""
    state, engine = await start_fake_engine(tokens_per_sec=10.0)
    state.capacity = 1
    state.max_queued = 2
    url = str(engine.make_url("")).rstrip("/")
    try:
        app, server, client = await start_router(
            [url], ["fake/llama-3-8b"],
            extra_args=["--engine-stats-interval", "0.2"],
        )
        try:
            # Oversubscribe: 3 concurrent (capacity 1) -> engine queue
            # visible on the next scrape.
            holders = [
                await _stream_until_stalled(client, "fake/llama-3-8b",
                                            max_tokens=50)
                for _ in range(3)
            ]
            await asyncio.sleep(0.5)  # one scrape: waiting>0 at inflight 3
            rejected_before = state.admission_rejected
            resp = await client.post(
                "/v1/chat/completions",
                json={"model": "fake/llama-3-8b", "stream": False,
                      "max_tokens": 2,
                      "messages": [{"role": "user", "content": "hi"}]},
            )
            assert resp.status == 429
            body = await resp.json()
            assert body["error"]["type"] == "fleet_overloaded"
            # The router shed; the engine's own admission never fired.
            assert state.admission_rejected == rejected_before
            for h in holders:
                h.close()
        finally:
            await client.close()
    finally:
        await engine.close()


# -- the acceptance chaos replay --------------------------------------------


# Arrivals of the chaos replay's schedule (seed 7, 8 s, 6 -> 60 -> 6 qps).
CHAOS_ARRIVALS = 276


async def test_fleet_chaos_replay_2_N_2():
    """20 fake engines, seeded 10x diurnal swing, 2→20→2 through drain
    mid-replay, kill + stall + 429-storm injected.  Asserts the three
    acceptance properties (see module docstring)."""
    h = FleetHarness(
        num_engines=20, seed=7,
        capacity=2, max_queued=8,
        tokens_per_sec=60.0, ttft=0.01, max_tokens=6,
        default_slots=8.0,  # < engine bound (10): router sheds first
        router_args=("--stream-idle-timeout-s", "1.0"),
    )
    await h.start(active=2)
    try:
        duration, base_qps, peak_qps = 8.0, 6.0, 60.0

        async def scale_up():
            await h.scale_to(20)

        async def scale_down():
            # Fire-and-forget: the drain wait must not stall the arrival
            # process; the harness holds the task for wait_background().
            h.scale_to_background(2)

        async def kill_engine0():
            h.inject(0, "refuse", count=-1)

        async def revive_engine0():
            h.clear_injection(0, "refuse")

        async def storm_engine1():
            h.inject(1, "reject_429", retry_after=1, count=6)

        async def storm_done():
            h.clear_injection(1, "reject_429")

        async def stall_engine5():
            h.inject(5, "stall_stream", after_tokens=2, count=2)

        async def stall_done():
            h.clear_injection(5, "stall_stream")

        await h.replay(
            duration_s=duration, base_qps=base_qps, peak_qps=peak_qps,
            events=[
                (1.8, kill_engine0),      # kill one of the two replicas
                (2.2, storm_engine1),     # 429-storm the survivor
                (2.6, revive_engine0),
                (3.0, scale_up),          # autoscale into the surge
                (3.2, storm_done),
                (4.0, stall_engine5),     # stall two streams at peak
                (4.6, stall_done),
                (5.5, scale_down),        # drain 18 replicas on the way down
            ],
        )
        # Let the drain finish before judging stream integrity and the
        # oracle's capacity timeline.
        await h.wait_background()

        report = h.report()
        # The arrival schedule is the seed's: the machine's load moves
        # no arrival and drops none.
        assert report["total"] == CHAOS_ARRIVALS, report
        assert report["total"] == sum(
            report[k] for k in ("completed", "shed_router", "shed_engine",
                                "error", "dropped")
        ), report

        # 1. Zero dropped in-flight streams — the only allowed drops are
        # the two stall-injected teardowns; every OTHER engine (drained
        # ones included) finished every stream it started.
        assert report["dropped"] <= 2, report
        for be in h.backends:
            if be.index == 5:
                continue
            assert be.state.aborted_requests == [], (
                f"engine {be.index} dropped streams {be.state.aborted_requests}"
            )

        # 2. The overload was real and the router was the firewall:
        # fleet-level sheds dominate engine-level 429s.
        assert report["shed_router"] > 0, report
        assert report["shed_router"] >= report["shed_engine"], report

        # 3. Goodput, in counts: what the router admitted, the fleet
        # served (the rest: the killed replica's refusals before its
        # breaker opened, engine-side 429s, the two stalled streams).
        # Against the capacity-model-perfect oracle the bound is loose on
        # purpose: the oracle's replicas serve at their nominal rate and
        # a loaded machine's do not (completed / oracle read 0.90 idle
        # and 0.7-0.9 with every core busy), so it guards against a
        # router that sheds what the fleet could have served, not
        # against the machine.
        admitted = report["total"] - report["shed_router"]
        assert report["completed"] >= 0.9 * admitted, report
        oracle = h.oracle_admitted()
        assert oracle > 0
        assert report["completed"] >= 0.6 * oracle, (
            f"goodput {report['completed']} < 0.6 * oracle {oracle:.1f}: "
            f"{report}"
        )

        # 4. Every engine-side 429 is preceded by router-side fleet sheds
        # in the same overload window, in the order the answers came in.
        violations = h.shed_ordering_violations()
        if violations:
            at = h.outcomes.index(violations[0])
            pytest.fail(
                f"{len(violations)} engine 429(s) without a preceding router "
                f"shed: {violations[:3]}; the fleet changed at outcomes "
                f"{h.fleet_changes}, this is outcome {at}, after "
                f"{[(o.kind, round(o.done_t, 3)) for o in h.outcomes[max(0, at - 12):at]]}; "
                f"{report}"
            )

        # 5. The scale cycle actually happened: 2 -> 20 -> 2.
        counts = [n for _, n in h.active_timeline]
        assert max(counts) == 20 and counts[0] == 2 and counts[-1] == 2
    finally:
        await h.close()


async def test_fleet_slice_group_member_kill_and_restart():
    """Slice-coherent lifecycle at fleet scale (docs/robustness.md
    "Slice lifecycle contract"): one fake slice group (leader + 2
    follower ordinals, member timeout 0.4s) serves among single-host
    replicas as ONE discovery endpoint.  Kill a follower mid-replay:
    the slice's /health fails within the member-timeout window, the
    router sheds ZERO 500s (breaker + retry budget + fleet admission
    absorb the refusals), and the group restarts and rejoins with a
    STRICTLY larger epoch."""
    import time as _time

    h = FleetHarness(
        num_engines=5, seed=11,
        capacity=2, max_queued=8,
        tokens_per_sec=80.0, ttft=0.01, max_tokens=5,
        default_slots=8.0,
        slice_members=3, slice_member_timeout_s=0.4,
    )
    await h.start(active=4)
    try:
        assert h.slice_group is not None
        epoch0 = h.slice_group.epoch
        leader_url = h.backends[0].url
        health_503 = {}

        async def kill_follower():
            h.kill_slice_member(1)
            t_kill = _time.monotonic()
            # Poll the leader's /health until the member failure fails
            # the WHOLE slice (the conjunction contract).
            async def poll():
                while True:
                    async with h.client.session.get(
                        f"{leader_url}/health"
                    ) as resp:
                        if resp.status == 503:
                            health_503["elapsed"] = (
                                _time.monotonic() - t_kill
                            )
                            return
                    await asyncio.sleep(0.05)

            await asyncio.wait_for(poll(), timeout=5.0)

        async def restart_group():
            h.restart_slice()

        await h.replay(
            duration_s=4.0, base_qps=4.0, peak_qps=14.0,
            events=[
                (1.0, kill_follower),
                (2.6, restart_group),
            ],
        )
        await h.wait_background()

        # 1. The slice's health failed within the member-timeout window
        # (generous CI slack on top of the 0.4s timeout).
        assert "elapsed" in health_503, "leader /health never went 503"
        assert health_503["elapsed"] < 0.4 + 1.5, health_503

        # 2. Zero 500s at the router: every request either completed or
        # was a structured shed — the breaker and retry budget absorbed
        # the failed slice's refusals, and nothing mid-stream dropped.
        report = h.report()
        assert report["total"] > 20, report
        assert report["error"] == 0, report
        assert report["dropped"] == 0, report
        assert report["completed"] > 0, report

        # 3. The group restarted and rejoined with a strictly larger
        # epoch, and the slice serves again.
        assert h.slice_group.epoch > epoch0
        async with h.client.session.get(f"{leader_url}/health") as resp:
            assert resp.status == 200
        assert h.slice_group.member_failures == {"member_silent": 1}
    finally:
        await h.close()


async def test_a_replay_loop_that_wakes_late_still_launches_the_seeds_schedule():
    """The chaos replay's arrivals on a clock that jumps from the start past
    the end (a machine that stalls this process through the replay's last
    arrivals): every one is launched, and none was before the jump."""
    h = FleetHarness(num_engines=20, seed=7, capacity=2, max_queued=8,
                     tokens_per_sec=60.0, ttft=0.01, max_tokens=6)
    launched = []

    async def one_request(**kwargs):
        launched.append(kwargs)

    h.one_request = one_request
    clock = iter([0.0, 0.0])  # where the replay starts; its first pass
    h.now = lambda: next(clock, 100.0)
    fired = []

    async def event():
        fired.append(5.5)

    await h.replay(duration_s=8.0, base_qps=6.0, peak_qps=60.0,
                   events=[(5.5, event)])
    assert len(launched) == CHAOS_ARRIVALS
    assert len(fired) == 1


async def test_harness_report_and_oracle_units():
    """Pure-math harness helpers: classification, oracle integration,
    shed-ordering detection (no servers involved)."""
    h = FleetHarness(num_engines=1, capacity=2, tokens_per_sec=60.0,
                     ttft=0.01, max_tokens=6)
    from production_stack_tpu.testing.fleet import Outcome

    h.active_timeline = [(0.0, 2)]
    # 10 arrivals in [0, 1): capacity = 2 engines * ~18.3 req/s -> oracle
    # caps at offered when under capacity.
    for i in range(10):
        h.outcomes.append(Outcome(i * 0.1, i * 0.1 + 0.2, "completed"))
    oracle = h.oracle_admitted(bin_s=1.0)
    assert oracle == pytest.approx(10.0)
    # Overload bin: 100 arrivals in one second vs ~36.6 capacity.
    h.outcomes = [
        Outcome(0.005 * i, 0.005 * i, "completed") for i in range(100)
    ]
    oracle = h.oracle_admitted(bin_s=1.0)
    assert oracle == pytest.approx(2 * h.per_engine_rate(), rel=0.01)

    # Shed ordering: an engine shed flags unless a router shed came before
    # it since the fleet last changed, or one of the harness's own faults
    # was armed when the request was sent.
    h.outcomes = [
        Outcome(1.0, 1.0, "shed_engine"),
        Outcome(2.0, 2.0, "shed_router"),
        Outcome(2.5, 2.5, "shed_engine"),
        # -- the fleet changes: a new window
        Outcome(3.0, 3.4, "completed"),
        Outcome(3.1, 3.5, "shed_engine"),
        # -- a fault is armed
        Outcome(4.1, 4.2, "shed_engine"),
    ]
    h.fleet_changes = [3, 5]
    h.fault_timeline = [(4.0, 0, True)]
    assert [o.done_t for o in h.shed_ordering_violations()] == [1.0, 3.5]

    assert h._classify_reject(
        429, json.dumps({"error": {"type": "fleet_overloaded"}}).encode()
    ) == "shed_router"
    assert h._classify_reject(
        429, json.dumps({"error": {"type": "overloaded"}}).encode()
    ) == "shed_engine"
    assert h._classify_reject(502, b"") == "error"
