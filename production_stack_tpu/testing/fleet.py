"""In-process fleet harness: 20+ fake engines behind a real router,
seeded diurnal traffic replay, scale-through-drain, fault injection.

The CI-scale proof rig for ROADMAP item 2 (fleet-level admission +
SLO autoscaling; SURVEY §2.6's "10 QPS x 32 workers" CI smoke, scaled
up): everything runs on one asyncio loop — N :class:`FakeEngineState`
backends on aiohttp TestServers, the REAL router app (capacity model,
fleet admission, breaker, stats plane all live) proxying to them, and a
seeded Poisson arrival process whose rate follows a diurnal curve that
swings ``peak_qps/base_qps`` (10x in the acceptance test).  No TPU, no
sockets beyond loopback, no sleeps beyond the replay clock.

What it measures (per request, classified at response time):

* ``completed``   — 200 and the stream ran to ``[DONE]`` (goodput)
* ``shed_router`` — 429 with error type ``fleet_overloaded`` (the
  capacity model shed at the router; docs/robustness.md)
* ``shed_engine`` — 429 with any other error type (the engine's own
  bounded admission tripped — in a healthy fleet these are strictly
  RARER than and PRECEDED by router sheds; see
  :meth:`FleetHarness.shed_ordering_violations` for what "preceded" is
  held to)
* ``error``       — 5xx / connect failure before any stream byte
* ``dropped``     — the stream STARTED and then died before ``[DONE]``
  (the one class the scale-through-drain guarantee forbids entirely)

Scale events run mid-replay: ``scale_to(n)`` adds replicas to discovery
(instant, like pods passing readiness); scale-down goes THROUGH THE
DRAIN PATH — endpoints leave discovery first (no new routing picks),
then ``POST /drain`` flips the backend to rejecting new work, and the
harness waits for its in-flight streams to finish before calling the
replica gone (the k8s preStop ordering PR 5 wired into helm).

Fault injection rides :meth:`FakeEngineState.inject`: ``kill`` (connect
refusal), ``stall`` (stream hangs mid-token), ``flap_429`` (a 429 storm
from one backend), all revertible mid-replay.

Determinism: arrivals (how many, when due, which lane), prompts and
injection schedules derive from one ``random.Random(seed)``, drawn
before the replay clock starts.  The wall clock enters through service
times and through what the router has learned of them, so what a test
may hold exactly are counts and orderings (the arrivals, every request
accounted for, zero drops, who shed first); a rate or a tail is the
machine's as much as the router's.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import random
import time
from typing import Callable, Dict, List, Optional, Tuple

from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.router.app import build_app
from production_stack_tpu.router.parser import parse_args
from production_stack_tpu.router.service_discovery import (
    DISCOVERY_SERVICE,
    EndpointInfo,
    StaticServiceDiscovery,
)
from production_stack_tpu.router.services.request_service.request import (
    ENGINE_STATS_SCRAPER,
)
from production_stack_tpu.testing.fake_engine import (
    FakeEngineState,
    FakeSliceGroup,
    build_fake_engine_app,
    build_fake_follower_app,
)

MODEL = "fleet/fake-llama"


class MutableServiceDiscovery(StaticServiceDiscovery):
    """Static discovery whose endpoint set changes at runtime — the
    harness's stand-in for pods joining/leaving a k8s Service as the
    autoscaler acts."""

    def add(self, url: str, models: List[str]) -> None:
        if any(ep.url == url for ep in self._endpoints):
            return
        self._endpoints.append(EndpointInfo(url=url, model_names=list(models)))

    def remove(self, url: str) -> None:
        self._endpoints = [ep for ep in self._endpoints if ep.url != url]


@dataclasses.dataclass
class Outcome:
    """One replayed request's fate (timestamps on the replay clock)."""

    arrived_t: float
    done_t: float
    kind: str            # completed | shed_router | shed_engine | error | dropped
    status: int = 0
    chunks: int = 0
    itl_p95: float = 0.0  # per-request p95 token gap (completed only)
    phase: str = "replay"  # warmup | replay


@dataclasses.dataclass
class FleetBackend:
    index: int
    state: FakeEngineState
    server: TestServer
    url: str = ""
    active: bool = False


class FleetHarness:
    """N fake engines + the real router, driven by a seeded replay."""

    def __init__(
        self,
        num_engines: int = 20,
        *,
        seed: int = 0,
        capacity: int = 2,
        max_queued: int = 8,
        tokens_per_sec: float = 60.0,
        ttft: float = 0.01,
        max_tokens: int = 6,
        router_args: Tuple[str, ...] = (),
        fleet_admission: bool = True,
        default_slots: float = 8.0,
        routing_logic: str = "least_loaded",
        engine_kwargs: Optional[Dict] = None,
        base_port: Optional[int] = None,
        slice_members: int = 0,
        slice_member_timeout_s: float = 0.5,
    ):
        self.num_engines = int(num_engines)
        self.seed = int(seed)
        self.capacity = int(capacity)
        self.max_queued = int(max_queued)
        self.tokens_per_sec = float(tokens_per_sec)
        self.ttft = float(ttft)
        self.max_tokens = int(max_tokens)
        self.router_args = tuple(router_args)
        self.fleet_admission = bool(fleet_admission)
        self.default_slots = float(default_slots)
        self.routing_logic = routing_logic
        # Extra FakeEngineState kwargs (e.g. the prefix-cache/prefill
        # cost model the multi-round workload turns on) applied to every
        # backend at start().
        self.engine_kwargs = dict(engine_kwargs or {})
        # Fixed backend ports (base_port + index) instead of ephemeral
        # ones: consistent-hash placement (SessionRouter) hashes backend
        # URLs, so random ports make hash placement — and therefore every
        # seeded A/B against it — nondeterministic across runs.
        self.base_port = base_port
        # Multi-host slice emulation: with slice_members >= 2, backend 0
        # becomes the LEADER of a fake slice group — ONE discovery
        # endpoint whose health is the conjunction of its members — and
        # the follower ordinals get health-only endpoints OUTSIDE
        # discovery (k8s only exposes the ordinal-0 client service).
        self.slice_members = int(slice_members)
        self.slice_member_timeout_s = float(slice_member_timeout_s)
        self.slice_group: Optional[FakeSliceGroup] = None
        self.slice_follower_servers: List[TestServer] = []
        self.rng = random.Random(self.seed)
        self.backends: List[FleetBackend] = []
        self.outcomes: List[Outcome] = []
        # (replay_t, active_count) steps — the oracle's capacity timeline.
        self.active_timeline: List[Tuple[float, int]] = []
        # (replay_t, engine_index, armed) — fault windows; an engine with
        # an armed capacity-destroying fault contributes zero capacity to
        # the oracle (an omniscient admission schedule cannot serve work
        # on a killed/stalled/429-flapping replica either).
        self.fault_timeline: List[Tuple[float, int, bool]] = []
        # How many outcomes were in when the harness last changed the fleet
        # (a scale event, a fault armed or cleared): where an overload
        # window starts, in the order things happened.
        self.fleet_changes: List[int] = []
        self._discovery: Optional[MutableServiceDiscovery] = None
        self._client: Optional[TestClient] = None
        self._router_server: Optional[TestServer] = None
        self._app = None
        self._t0: float = 0.0
        # Strong refs to fire-and-forget event tasks (an unreferenced
        # ensure_future can be GC'd or destroyed pending at loop close);
        # wait_background() drains them before report()/close().
        self._background: List[asyncio.Task] = []

    # -- lifecycle ---------------------------------------------------------

    async def start(self, active: int = 2) -> None:
        if self.slice_members >= 2:
            self.slice_group = FakeSliceGroup(
                num_members=self.slice_members,
                member_timeout_s=self.slice_member_timeout_s,
            )
        for i in range(self.num_engines):
            state = FakeEngineState(
                model=MODEL,
                tokens_per_sec=self.tokens_per_sec,
                ttft=self.ttft,
                seed=self.seed + i,
                capacity=self.capacity,
                max_queued=self.max_queued,
                slice_group=self.slice_group if i == 0 else None,
                **self.engine_kwargs,
            )
            if self.base_port is not None:
                server = TestServer(
                    build_fake_engine_app(state), port=self.base_port + i
                )
            else:
                server = TestServer(build_fake_engine_app(state))
            await server.start_server()
            be = FleetBackend(index=i, state=state, server=server)
            be.url = str(server.make_url("")).rstrip("/")
            self.backends.append(be)

        if self.slice_group is not None:
            # Follower probe endpoints (ordinals 1..n-1): live servers so
            # probe/drain paths are real HTTP, but never in discovery —
            # the slice is ONE endpoint fronted by its leader.
            leader_state = self.backends[0].state
            for ordinal in range(1, self.slice_members):
                fsrv = TestServer(
                    build_fake_follower_app(leader_state, ordinal)
                )
                await fsrv.start_server()
                self.slice_follower_servers.append(fsrv)

        initial = self.backends[:active]
        for be in initial:
            be.active = True
        argv = [
            "--static-backends", ",".join(be.url for be in initial),
            "--static-models", ",".join(MODEL for _ in initial),
            "--routing-logic", self.routing_logic,
            "--engine-stats-interval", "0.25",
            "--request-stats-window", "3",
            "--fleet-default-slots", str(self.default_slots),
            *(() if self.fleet_admission else ("--no-fleet-admission",)),
            *self.router_args,
        ]
        args = parse_args(argv)
        self._app = build_app(args)
        # Swap in the mutable discovery (same object model the dynamic
        # config watcher uses) so scale events are a list mutation, and
        # re-point the scraper at it.
        registry = self._app["registry"]
        discovery = MutableServiceDiscovery(
            [be.url for be in initial], [[MODEL] for _ in initial]
        )
        registry.replace(DISCOVERY_SERVICE, lambda: discovery)
        registry.get(ENGINE_STATS_SCRAPER).service_discovery = discovery
        self._discovery = discovery
        self._router_server = TestServer(self._app)
        await self._router_server.start_server()
        self._client = TestClient(self._router_server)
        self._t0 = time.monotonic()
        self.active_timeline.append((0.0, active))

    async def close(self) -> None:
        # Drain outstanding background scale tasks BEFORE tearing the
        # backends down — an exception path that skipped
        # wait_background() must not close servers out from under a
        # mid-drain task (unretrieved task exceptions at loop close).
        for task in self._background:
            task.cancel()
        if self._background:
            await asyncio.gather(*self._background, return_exceptions=True)
            self._background = []
        if self._client is not None:
            await self._client.close()
        for be in self.backends:
            await be.server.close()
        for fsrv in self.slice_follower_servers:
            await fsrv.close()

    @property
    def client(self) -> TestClient:
        assert self._client is not None, "harness not started"
        return self._client

    @property
    def registry(self):
        return self._app["registry"]

    def now(self) -> float:
        return time.monotonic() - self._t0

    def active_count(self) -> int:
        return sum(1 for be in self.backends if be.active)

    # -- scaling -----------------------------------------------------------

    async def scale_to(self, n: int, drain_timeout_s: float = 5.0) -> None:
        """Scale the active replica set to ``n``.  Up: replicas join
        discovery immediately.  Down: excess replicas leave discovery,
        then DRAIN — new work is rejected at the backend while in-flight
        streams finish; the replica only counts as gone once idle."""
        assert self._discovery is not None
        n = max(0, min(n, self.num_engines))
        self.fleet_changes.append(len(self.outcomes))
        current = [be for be in self.backends if be.active]
        if n > len(current):
            for be in self.backends:
                if not be.active and n > len(current):
                    be.state.draining = False  # re-join after an earlier drain
                    be.active = True
                    self._discovery.add(be.url, [MODEL])
                    current.append(be)
        elif n < len(current):
            victims = current[n:]
            for be in victims:
                # k8s ordering: endpoint leaves the Service FIRST (no new
                # routing picks), preStop /drain second.
                self._discovery.remove(be.url)
            # Let racing routing decisions (endpoint list snapshots taken
            # before the removal) land before the backend starts 503ing.
            await asyncio.sleep(0.05)
            for be in victims:
                async with self.client.session.post(f"{be.url}/drain") as resp:
                    await resp.read()
            deadline = time.monotonic() + drain_timeout_s
            for be in victims:
                while be.state.num_running > 0 and time.monotonic() < deadline:
                    await asyncio.sleep(0.01)
                be.active = False
        self.active_timeline.append((self.now(), self.active_count()))

    def scale_to_background(self, n: int) -> asyncio.Task:
        """Fire a scale event without blocking the caller (the arrival
        process must not stall on a drain wait — k8s scales down
        asynchronously too).  The task is held and awaited by
        wait_background()."""
        task = asyncio.ensure_future(self.scale_to(n))
        self._background.append(task)
        return task

    async def wait_background(self, timeout_s: float = 10.0) -> None:
        """Drain outstanding background scale events (call before
        report()/oracle math — a still-pending drain means the capacity
        timeline is not final)."""
        if self._background:
            await asyncio.wait(self._background, timeout=timeout_s)
            self._background = []

    # -- faults ------------------------------------------------------------

    def _fault(self, index: int, armed: bool) -> None:
        self.fault_timeline.append((self.now(), index, armed))
        self.fleet_changes.append(len(self.outcomes))

    def inject(self, index: int, kind: str, **params) -> None:
        self.backends[index].state.inject(kind, **params)
        self._fault(index, True)

    def clear_injection(self, index: int, kind: str) -> None:
        self.backends[index].state.clear_injection(kind)
        self._fault(index, False)

    def kill_slice_member(self, ordinal: int) -> None:
        """Kill one follower of the fake slice group: its acks freeze,
        the leader's /health fails within the member-timeout window, and
        the slice's data plane starts refusing (the fatal-exited leader
        as the router sees it).  The whole slice — one endpoint, backend
        0 — contributes zero oracle capacity while failed."""
        assert self.slice_group is not None, "harness has no slice group"
        self.slice_group.kill_member(ordinal)
        self._fault(0, True)

    def restart_slice(self) -> None:
        """The parallel k8s group restart: members revive into one fresh
        incarnation with a STRICTLY larger epoch and the endpoint serves
        again (the breaker's half-open probe re-admits it)."""
        assert self.slice_group is not None, "harness has no slice group"
        self.slice_group.restart()
        self.backends[0].state.draining = False
        self._fault(0, False)

    # -- traffic -----------------------------------------------------------

    async def one_request(
        self, *, phase: str = "replay", priority: Optional[int] = None,
        max_tokens: Optional[int] = None,
    ) -> Outcome:
        """One streamed chat completion through the router, classified."""
        arrived = self.now()
        body = {
            "model": MODEL,
            "stream": True,
            "max_tokens": max_tokens if max_tokens is not None else self.max_tokens,
            "messages": [
                {"role": "user", "content": f"fleet probe {self.rng.random():.8f}"}
            ],
        }
        if priority is not None:
            body["priority"] = priority
        chunks = 0
        token_times: List[float] = []
        saw_done = False
        started = False
        status = 0
        try:
            resp = await self.client.post("/v1/chat/completions", json=body)
            status = resp.status
            if status != 200:
                payload = await resp.read()
                kind = self._classify_reject(status, payload)
                return self._record(
                    Outcome(arrived, self.now(), kind, status=status, phase=phase)
                )
            buf = b""
            async for chunk in resp.content.iter_any():
                started = True
                buf += chunk
                while b"\n\n" in buf:
                    frame, buf = buf.split(b"\n\n", 1)
                    if not frame.startswith(b"data: "):
                        continue
                    if frame[6:].strip() == b"[DONE]":
                        saw_done = True
                    else:
                        chunks += 1
                        token_times.append(time.monotonic())
        except Exception:
            kind = "dropped" if started else "error"
            return self._record(
                Outcome(arrived, self.now(), kind, status=status,
                        chunks=chunks, phase=phase)
            )
        if not saw_done:
            return self._record(
                Outcome(arrived, self.now(), "dropped", status=status,
                        chunks=chunks, phase=phase)
            )
        gaps = sorted(b - a for a, b in zip(token_times, token_times[1:]))
        p95 = gaps[int(0.95 * (len(gaps) - 1))] if gaps else 0.0
        return self._record(
            Outcome(arrived, self.now(), "completed", status=200,
                    chunks=chunks, itl_p95=p95, phase=phase)
        )

    async def one_embed_request(
        self, *, phase: str = "replay", texts: Optional[List[str]] = None,
        repeat_pool: int = 0,
    ) -> Outcome:
        """One /v1/embeddings request through the router's encode lane,
        classified with the same Outcome vocabulary as generation.
        ``repeat_pool`` > 0 draws inputs from a small fixed pool (the
        repeat-heavy trace the semantic cache exists for) instead of
        unique probe strings."""
        arrived = self.now()
        if texts is None:
            if repeat_pool > 0:
                texts = [f"embed corpus doc {self.rng.randrange(repeat_pool)}"]
            else:
                texts = [f"embed probe {self.rng.random():.8f}"]
        status = 0
        try:
            resp = await self.client.post(
                "/v1/embeddings", json={"model": MODEL, "input": texts}
            )
            status = resp.status
            payload = await resp.read()
        except Exception:
            return self._record(
                Outcome(arrived, self.now(), "error", status=status,
                        phase=phase)
            )
        if status != 200:
            kind = self._classify_reject(status, payload)
            return self._record(
                Outcome(arrived, self.now(), kind, status=status, phase=phase)
            )
        data = json.loads(payload).get("data", [])
        kind = "completed" if len(data) == len(texts) else "error"
        return self._record(
            Outcome(arrived, self.now(), kind, status=status,
                    chunks=len(data), phase=phase)
        )

    @staticmethod
    def _classify_reject(status: int, payload: bytes) -> str:
        if status != 429:
            return "error"
        try:
            err = json.loads(payload).get("error", {})
        except (ValueError, AttributeError):
            err = {}
        return (
            "shed_router" if err.get("type") == "fleet_overloaded"
            else "shed_engine"
        )

    def _record(self, outcome: Outcome) -> Outcome:
        self.outcomes.append(outcome)
        return outcome

    def qps_at(self, t: float, duration: float, base: float, peak: float) -> float:
        """The diurnal rate curve: base at the edges, peak mid-replay
        (half-cosine — one compressed day)."""
        frac = 0.5 * (1.0 - math.cos(2.0 * math.pi * min(1.0, max(0.0, t / duration))))
        return base + (peak - base) * frac

    async def replay(
        self,
        *,
        duration_s: float,
        base_qps: float,
        peak_qps: float,
        events: Optional[List[Tuple[float, Callable]]] = None,
        phase: str = "replay",
        low_priority_frac: float = 0.0,
        embed_frac: float = 0.0,
        embed_repeat_pool: int = 0,
    ) -> None:
        """Seeded diurnal replay.  ``events`` is a list of
        ``(replay_t, async_callable)`` fired in order as the replay
        clock passes each time (scale events, fault injections).
        ``embed_frac`` sends that fraction of arrivals down the encode
        lane (/v1/embeddings) instead of chat — the mixed
        generation+embed workload the per-lane admission contract is
        about; ``embed_repeat_pool`` makes the embed side repeat-heavy
        (semantic-cache fodder)."""
        events = sorted(events or [], key=lambda e: e[0])
        # The whole arrival schedule is drawn before the clock starts, so
        # it is the seed's and nothing else's: a loop that wakes late (a
        # loaded machine) launches what is overdue and catches up, where
        # drawing each gap from the moment the loop noticed the last
        # arrival let the machine's load thin the offered traffic.
        arrivals: List[Tuple[float, str, Optional[int]]] = []
        rate = self.qps_at(0.0, duration_s, base_qps, peak_qps)
        t = self.rng.expovariate(rate) if rate > 0 else duration_s
        while t < duration_s:
            lane, priority = "chat", None
            if embed_frac and self.rng.random() < embed_frac:
                lane = "embed"
            elif low_priority_frac and self.rng.random() < low_priority_frac:
                priority = 1
            arrivals.append((t, lane, priority))
            rate = self.qps_at(t, duration_s, base_qps, peak_qps)
            t += self.rng.expovariate(rate) if rate > 0 else duration_s
        tasks: List[asyncio.Task] = []
        t_start = self.now()
        next_event = next_arrival = 0
        while True:
            # The end of the replay is an instant like any other the loop
            # may wake behind: what was due before it is launched first.
            t = min(self.now() - t_start, duration_s)
            while next_event < len(events) and events[next_event][0] <= t:
                await events[next_event][1]()
                next_event += 1
            while next_arrival < len(arrivals) and arrivals[next_arrival][0] <= t:
                _, lane, priority = arrivals[next_arrival]
                next_arrival += 1
                if lane == "embed":
                    coro = self.one_embed_request(
                        phase=phase, repeat_pool=embed_repeat_pool
                    )
                else:
                    coro = self.one_request(phase=phase, priority=priority)
                tasks.append(asyncio.ensure_future(coro))
            if t >= duration_s:
                break
            wake = min(
                arrivals[next_arrival][0] if next_arrival < len(arrivals)
                else duration_s,
                duration_s,
                events[next_event][0] if next_event < len(events) else duration_s,
            )
            await asyncio.sleep(max(0.001, min(wake - t, 0.25)))
        # Fire any remaining events (e.g. a trailing scale-down) before
        # waiting out the in-flight tail.
        while next_event < len(events):
            await events[next_event][1]()
            next_event += 1
        if tasks:
            await asyncio.wait(tasks, timeout=30.0)

    async def warmup(self, *, burst: int = 0, duration_s: float = 1.0) -> None:
        """Teach the capacity model each ACTIVE backend's bound: a short
        saturating burst whose engine 429s clamp the per-backend slot
        estimates (outcomes labeled phase="warmup" so measured-replay
        assertions exclude them).  This is the steady state a production
        fleet reaches after its first minutes of traffic."""
        n = burst or (self.active_count() * (self.capacity + self.max_queued) * 2)
        tasks = [
            asyncio.ensure_future(self.one_request(phase="warmup"))
            for _ in range(n)
        ]
        await asyncio.wait(tasks, timeout=max(duration_s * 10, 10.0))

    # -- analysis ----------------------------------------------------------

    def report(self, phase: str = "replay") -> Dict[str, object]:
        outs = [o for o in self.outcomes if o.phase == phase]
        by_kind: Dict[str, int] = {}
        for o in outs:
            by_kind[o.kind] = by_kind.get(o.kind, 0) + 1
        completed = [o for o in outs if o.kind == "completed"]
        itl = sorted(o.itl_p95 for o in completed if o.itl_p95 > 0)
        return {
            "total": len(outs),
            "completed": by_kind.get("completed", 0),
            "shed_router": by_kind.get("shed_router", 0),
            "shed_engine": by_kind.get("shed_engine", 0),
            "error": by_kind.get("error", 0),
            "dropped": by_kind.get("dropped", 0),
            "admitted_itl_p95_s": (
                itl[int(0.95 * (len(itl) - 1))] if itl else 0.0
            ),
        }

    def per_engine_rate(self) -> float:
        """Nominal full-throughput request rate of ONE replica: the fake
        engine's token throughput is capacity-bound (token intervals
        stretch with oversubscription), so rate = capacity * tps / tokens
        once TTFT is amortized."""
        service_s = self.ttft + self.max_tokens / self.tokens_per_sec
        return self.capacity / service_s

    def _active_at(self, t: float) -> int:
        n = self.active_timeline[0][1] if self.active_timeline else 0
        for ts, count in self.active_timeline:
            if ts <= t:
                n = count
            else:
                break
        return n

    def _faulted_at(self, t: float) -> int:
        """Engines with an armed fault at replay time ``t``."""
        armed: Dict[int, bool] = {}
        for ts, idx, on in self.fault_timeline:
            if ts <= t:
                armed[idx] = on
        return sum(1 for on in armed.values() if on)

    def oracle_admitted(
        self, phase: str = "replay", bin_s: float = 0.5,
        derate: float = 1.0,
    ) -> float:
        """The capacity-model-PERFECT admission schedule's goodput: per
        arrival-time bin, min(offered, active_capacity) requests — an
        omniscient router admitting exactly what the active replicas can
        serve and shedding the rest at zero cost.  ``derate`` scales the
        nominal per-replica rate (CI CPUs are not lab-quiet)."""
        outs = [o for o in self.outcomes if o.phase == phase]
        if not outs:
            return 0.0
        t_max = max(o.arrived_t for o in outs)
        t_min = min(o.arrived_t for o in outs)
        rate = self.per_engine_rate() * derate
        total = 0.0
        t = t_min
        while t < t_max + bin_s:
            offered = sum(1 for o in outs if t <= o.arrived_t < t + bin_s)
            mid = t + bin_s / 2
            healthy = max(0, self._active_at(mid) - self._faulted_at(mid))
            cap = healthy * rate * bin_s
            total += min(float(offered), cap)
            t += bin_s
        return total

    def shed_ordering_violations(self, phase: str = "replay") -> List[Outcome]:
        """Engine-side 429s that no router-side fleet shed came before:
        the overload-firewall ordering guarantee says this list is empty —
        the router sheds first, the engines' own bounds are the
        belt-and-braces layer behind it.

        "Before" is the order the answers came in (``outcomes`` is in that
        order), and the overload window an engine 429 belongs to starts
        where the harness last changed the fleet (``fleet_changes``): no
        span of the wall clock is measured, so a machine that stalls this
        process moves nothing.
        A request sent while one of the harness's own faults was armed is
        not held to the order: a 429 storm's rejections are injected, and
        until the breaker has counted a killed replica out, its slots are
        headroom the gate admits on and the failover lands on the survivor
        past the gate (the router cannot shed before what it has not been
        told; ``oracle_admitted`` takes such a replica's capacity out the
        same way)."""
        violations = []
        router_shed_in_window = False
        for i, o in enumerate(self.outcomes):
            if i in self.fleet_changes:
                router_shed_in_window = False
            if o.phase != phase:
                continue
            if o.kind == "shed_router":
                router_shed_in_window = True
            elif (
                o.kind == "shed_engine"
                and not router_shed_in_window
                and not self._faulted_at(o.arrived_t)
            ):
                violations.append(o)
        return violations
