"""Slice-coherent lifecycle (docs/robustness.md "Slice lifecycle
contract"): lockstep group liveness, the group epoch split-brain guard,
and the follower->leader slice-wide drain relay.

Three layers, all tier-1 without a TPU or multiprocess collectives:

* control-plane units — LocalAckStore, epoch minting/adoption/mismatch,
  ack throttling, GroupLivenessMonitor detection with a fake clock,
  drain-relay once-firing, the follower slice-guard;
* the FAKE slice group (testing/fake_engine.py) over real HTTP — leader
  /health is the conjunction of member liveness, a follower's POST
  /drain relays and the leader drains the group, restarts mint strictly
  larger epochs, the metric mirror carries live values;
* the REAL leader machinery — an AsyncEngine with a real LockstepChannel
  (broadcast stubbed to a recorder; the side channel is a LocalAckStore)
  proves the ISSUE acceptance bullets end to end: a member going silent
  mid-stream fails /health within --slice-member-timeout-s and
  fatal-exits the group; a drain relayed mid-stream completes the
  in-flight stream before any member exits.
"""

import asyncio
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.parallel import distributed
from production_stack_tpu.engine.parallel.distributed import (
    DistributedEnv,
    GroupEpochMismatch,
    GroupLivenessMonitor,
    LocalAckStore,
    LockstepChannel,
    StepEvents,
    new_epoch,
)
from production_stack_tpu.testing.fake_engine import (
    FakeEngineState,
    FakeSliceGroup,
    build_fake_engine_app,
    build_fake_follower_app,
)


def _leader(store, timeout=1.0):
    return LockstepChannel(
        DistributedEnv("x:1", 3, 0), member_timeout_s=timeout,
        ack_store=store,
    )


def _follower(store, pid=1, timeout=1.0):
    return LockstepChannel(
        DistributedEnv("x:1", 3, pid), member_timeout_s=timeout,
        ack_store=store,
    )


# -- control-plane units -----------------------------------------------------


def test_new_epoch_strictly_increases():
    epochs = [new_epoch() for _ in range(5)]
    assert all(b > a for a, b in zip(epochs, epochs[1:]))


def test_publish_stamps_epoch_and_seq(monkeypatch):
    sent = []
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: sent.append(obj)
    )
    leader = _leader(LocalAckStore())
    leader.publish(StepEvents())
    leader.publish(StepEvents(shutdown=True))
    assert [e.seq for e in sent] == [1, 2]
    assert sent[0].epoch == sent[1].epoch == leader.epoch > 0


def test_follower_adopts_epoch_and_acks(monkeypatch):
    store = LocalAckStore()
    follower = _follower(store)
    ev = StepEvents()
    ev.epoch, ev.seq = 12345, 1
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: ev
    )
    follower.receive()
    assert follower.epoch == 12345
    assert store.get(distributed._ack_key(12345, 1, 1)) == "1"
    # Acks are throttled: an immediate second receive writes no new
    # ordinal, but the ordinal-1 ack stands.
    ev.seq = 2
    follower.receive()
    assert store.get(distributed._ack_key(12345, 1, 2)) is None


def test_epoch_change_after_adoption_is_fatal(monkeypatch):
    follower = _follower(LocalAckStore())
    ev = StepEvents()
    ev.epoch, ev.seq = 100, 1
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: ev
    )
    follower.receive()
    ev2 = StepEvents()
    ev2.epoch, ev2.seq = 200, 1  # a NEWER group incarnation
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: ev2
    )
    with pytest.raises(GroupEpochMismatch):
        follower.receive()


def test_midstream_join_is_fatal(monkeypatch):
    """A restarted member's first-ever event arriving at seq > 1 means it
    is attaching to a RUNNING group whose state it does not share."""
    follower = _follower(LocalAckStore())
    ev = StepEvents()
    ev.epoch, ev.seq = 100, 7
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: ev
    )
    with pytest.raises(GroupEpochMismatch):
        follower.receive()


def test_follower_loop_exits_nonzero_on_epoch_mismatch(monkeypatch):
    exits = []
    monkeypatch.setattr(distributed, "fatal_exit", exits.append)

    class MismatchChannel:
        denv = DistributedEnv("x:1", 2, 1)

        def receive(self):
            raise GroupEpochMismatch("epoch changed 1 -> 2")

    class NullEngine:
        def has_unfinished(self):
            return False

    distributed.follower_loop(NullEngine(), MismatchChannel())
    assert exits == [1]


def test_heartbeat_outpaces_member_timeout():
    """The idle heartbeat must publish several times per member-timeout
    window, or an idle group would trip the monitor between beats."""
    leader = _leader(LocalAckStore(), timeout=3.0)
    assert leader.heartbeat_seconds <= 1.0
    # Liveness off: the configured heartbeat stands.
    loose = LockstepChannel(
        DistributedEnv("x:1", 2, 0), member_timeout_s=0,
        ack_store=LocalAckStore(),
    )
    assert loose.heartbeat_seconds == 10.0


def test_monitor_detects_silent_member_with_fake_clock(monkeypatch):
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: obj
    )
    store = LocalAckStore()
    clock = [0.0]
    leader = _leader(store, timeout=1.0)
    mon = GroupLivenessMonitor(
        leader, exit_on_failure=False, clock=lambda: clock[0]
    )
    # Unarmed before the first publish: silence is not failure (members
    # have nothing to ack during a long leader boot/compile).
    clock[0] += 100.0
    mon.poll_once()
    assert mon.problem() is None
    leader.publish(StepEvents())
    # Both members ack -> healthy; ages reset on progress.
    store.set(distributed._ack_key(leader.epoch, 1, 1), "1")
    store.set(distributed._ack_key(leader.epoch, 2, 1), "1")
    mon.poll_once()
    assert mon.problem() is None
    assert mon.member_ack_ages() == {1: 0.0, 2: 0.0}
    # Member 2 keeps acking, member 1 goes silent past the timeout.
    clock[0] += 1.5
    store.set(distributed._ack_key(leader.epoch, 2, 2), "1")
    mon.poll_once()
    problem = mon.problem()
    assert problem is not None and "member 1" in problem
    assert mon.member_failures == {"member_silent": 1}
    assert mon.member_ack_ages()[1] == pytest.approx(1.5)


def test_monitor_drain_relay_fires_once(monkeypatch):
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: obj
    )
    store = LocalAckStore()
    leader = _leader(store, timeout=100.0)
    leader.publish(StepEvents())
    relays = []
    mon = GroupLivenessMonitor(
        leader, exit_on_failure=False,
        on_drain_relay=lambda: relays.append(1),
    )
    follower = _follower(store, timeout=100.0)
    follower.epoch = leader.epoch
    follower._epoch_adopted = True
    assert follower.relay_drain()
    assert follower.drain_relayed
    mon.poll_once()
    mon.poll_once()
    assert relays == [1]
    assert mon.drain_relays == 1


def test_drain_relayed_before_epoch_adoption_survives(monkeypatch):
    """A SIGTERM landing while the leader is still booting relays under
    epoch 0 (nothing polls it); adoption must re-key the intent so it is
    never silently lost."""
    store = LocalAckStore()
    follower = _follower(store)
    assert follower.relay_drain()  # pre-adoption: keyed under epoch 0
    ev = StepEvents()
    ev.epoch, ev.seq = 9000, 1
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: ev
    )
    follower.receive()
    assert store.get(distributed._drain_key(9000, 1)) is not None


def test_monitor_holds_relay_until_callback_wired(monkeypatch):
    """A relay observed before on_drain_relay is assigned (the leader's
    start()->lifecycle window) must not be consumed-and-dropped."""
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: obj
    )
    store = LocalAckStore()
    leader = _leader(store, timeout=100.0)
    leader.publish(StepEvents())
    store.set(distributed._drain_key(leader.epoch, 1), "1")
    mon = GroupLivenessMonitor(leader, exit_on_failure=False)
    mon.poll_once()
    assert mon.drain_relays == 0  # held, not dropped
    relays = []
    mon.on_drain_relay = lambda: relays.append(1)
    mon.poll_once()
    assert relays == [1] and mon.drain_relays == 1


def test_epoch_mismatch_is_reported_to_the_observed_groups_leader(
    monkeypatch,
):
    """The follower that fatal-exits on a mismatch leaves a marker the
    OBSERVED group's leader counts — the fleet can tell split-brain
    restarts from plain silence
    (tpu:lockstep_member_failures_total{reason="epoch_mismatch"})."""
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: obj
    )
    store = LocalAckStore()
    leader = _leader(store, timeout=100.0)
    leader.publish(StepEvents())
    # A follower of a DEAD incarnation observes the new group's events.
    stale_follower = _follower(store)
    stale_follower.epoch = leader.epoch - 1
    stale_follower._epoch_adopted = True
    ev = StepEvents()
    ev.epoch, ev.seq = leader.epoch, 5

    def recv_stale(obj, is_source):
        return ev

    monkeypatch.setattr(distributed, "broadcast_pyobj", recv_stale)
    with pytest.raises(GroupEpochMismatch):
        stale_follower.receive()
    mon = GroupLivenessMonitor(leader, exit_on_failure=False)
    mon.poll_once()
    mon.poll_once()
    assert mon.member_failures == {"epoch_mismatch": 1}


def test_monitor_thread_marks_group_failed_and_exits(monkeypatch):
    """The live monitor thread: a silent member flips problem(), writes
    the group-fail marker (live followers poll it off-collective), and
    fatal-exits the leader — the bounded fail-and-restart."""
    exits = []
    monkeypatch.setattr(distributed, "fatal_exit", exits.append)
    monkeypatch.setattr(
        distributed, "broadcast_pyobj", lambda obj, is_source: obj
    )
    store = LocalAckStore()
    leader = _leader(store, timeout=0.3)
    leader.publish(StepEvents())
    mon = GroupLivenessMonitor(leader)  # exit_on_failure=True
    mon.start()
    try:
        deadline = time.monotonic() + 5.0
        while not exits and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        mon.stop()
    assert exits == [1]
    assert mon.problem() is not None
    assert store.get(distributed._fail_key(leader.epoch)) is not None


def test_slice_guard_exits_on_group_fail_marker(monkeypatch):
    from production_stack_tpu.engine.server.api_server import _slice_guard

    exits = []
    monkeypatch.setattr(distributed, "fatal_exit", exits.append)
    store = LocalAckStore()
    follower = _follower(store)
    follower.epoch = 77
    follower._epoch_adopted = True
    stop = threading.Event()
    t = threading.Thread(target=_slice_guard, args=(follower, stop))
    t.start()
    try:
        follower.mark_group_failed("member 2 silent")
        deadline = time.monotonic() + 5.0
        while not exits and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        stop.set()
        t.join(5)
    assert exits == [1]


# -- the fake slice group over real HTTP -------------------------------------


async def _start_app(app):
    server = TestServer(app)
    await server.start_server()
    return server, TestClient(server)


async def test_fake_slice_health_is_member_conjunction():
    group = FakeSliceGroup(num_members=3, member_timeout_s=0.3)
    state = FakeEngineState(slice_group=group, tokens_per_sec=500.0)
    server, client = await _start_app(build_fake_engine_app(state))
    try:
        resp = await client.get("/health")
        assert resp.status == 200
        group.kill_member(2)
        t_kill = time.monotonic()
        # /health fails within the member-timeout window (+ CI slack).
        while (await client.get("/health")).status == 200:
            assert time.monotonic() - t_kill < 2.0
            await asyncio.sleep(0.05)
        elapsed = time.monotonic() - t_kill
        assert elapsed < 2.0, elapsed
        # Data plane refuses (the fatal-exited leader as the router
        # sees it) — never a clean completion from a half-dead group.
        with pytest.raises(Exception):
            await client.post(
                "/v1/completions",
                json={"model": "m", "prompt": "x", "max_tokens": 2},
            )
        # Parallel group restart: strictly larger epoch, healthy again.
        epoch0 = group.epoch
        group.restart()
        assert group.epoch > epoch0
        assert (await client.get("/health")).status == 200
        text = await (await client.get("/metrics")).text()
        assert f"tpu:lockstep_group_epoch {float(group.epoch)}" in text
        assert (
            'tpu:lockstep_member_failures_total{reason="member_silent"} 1.0'
            in text
        )
    finally:
        await client.close()


async def test_fake_follower_drain_relays_and_stream_completes():
    """The slice-wide drain: POST /drain on a FOLLOWER relays to the
    leader; the in-flight stream completes before the group 'exits'
    (drain semantics), and new work is refused."""
    group = FakeSliceGroup(num_members=2, member_timeout_s=5.0)
    state = FakeEngineState(slice_group=group, tokens_per_sec=100.0)
    server, client = await _start_app(build_fake_engine_app(state))
    fsrv, fclient = await _start_app(build_fake_follower_app(state, 1))
    try:
        stream = await client.post(
            "/v1/completions",
            json={"model": "m", "prompt": "hold", "max_tokens": 30,
                  "stream": True},
        )
        assert stream.status == 200
        await stream.content.readany()

        resp = await fclient.post("/drain")
        assert resp.status == 200
        assert (await resp.json())["relayed"] is True
        assert group.drain_relays == 1
        assert (await fclient.get("/ready")).status == 503

        # The in-flight stream runs to [DONE] even though the leader is
        # draining — the whole point of relaying instead of exiting.
        body = await stream.content.read()
        assert b"[DONE]" in body
        # New data-plane work is refused while the group drains out.
        resp = await client.post(
            "/v1/completions",
            json={"model": "m", "prompt": "new", "max_tokens": 2},
        )
        assert resp.status == 503
        text = await (await client.get("/metrics")).text()
        assert "tpu:slice_drain_relays_total 1.0" in text
    finally:
        await fclient.close()
        await client.close()


# -- the real leader machinery (AsyncEngine + real LockstepChannel) ----------


def _tiny_leader_engine(store, member_timeout_s):
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    channel = LockstepChannel(
        DistributedEnv("x:1", 2, 0),
        member_timeout_s=member_timeout_s,
        ack_store=store,
    )
    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 256,
           "cache.num_blocks": 128},
    )
    engine = AsyncEngine(config, lockstep=channel)
    assert engine.slice_monitor is not None
    return engine, channel


class _FakeFollower:
    """Acks the leader's published seq on a thread, like a live member's
    receive() path; stop() models the member dying."""

    def __init__(self, store, channel, pid=1, interval=0.05):
        self.store, self.channel, self.pid = store, channel, pid
        self.interval = interval
        self._ordinal = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(5)

    def _run(self):
        while not self._stop.wait(self.interval):
            if self.channel.seq == 0:
                continue
            self._ordinal += 1
            self.store.set(
                distributed._ack_key(
                    self.channel.epoch, self.pid, self._ordinal
                ),
                str(self.channel.seq),
            )


async def _start_engine_app(engine):
    from production_stack_tpu.engine.server.api_server import build_engine_app

    app = build_engine_app(engine, served_model="tiny-llama")
    server = TestServer(app)
    await server.start_server()
    return app, server, TestClient(server)


async def test_leader_health_fails_within_member_timeout(monkeypatch):
    """ISSUE acceptance: follower killed mid-stream -> leader /health
    goes 503 within --slice-member-timeout-s (plus poll/CI slack) and
    the group fatal-exits into a restart with the fail marker set."""
    exits = []
    monkeypatch.setattr(distributed, "fatal_exit", exits.append)
    sent = []
    monkeypatch.setattr(
        distributed, "broadcast_pyobj",
        lambda obj, is_source: sent.append(obj),
    )
    store = LocalAckStore()
    timeout_s = 0.8
    engine, channel = _tiny_leader_engine(store, timeout_s)
    follower = _FakeFollower(store, channel)
    follower.start()
    app, server, client = await _start_engine_app(engine)
    try:
        # A live stream on the slice while the member dies.
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "long stream",
                  "max_tokens": 400, "ignore_eos": True, "stream": True},
        )
        assert resp.status == 200
        await resp.content.readany()
        assert (await client.get("/health")).status == 200

        follower.stop()  # the member dies mid-stream
        t_dead = time.monotonic()
        while (await client.get("/health")).status == 200:
            assert time.monotonic() - t_dead < timeout_s + 2.0, \
                "health never failed"
            await asyncio.sleep(0.05)
        elapsed = time.monotonic() - t_dead
        # Detection needs silence > timeout; bound the excess.
        assert elapsed < timeout_s + 2.0, elapsed
        body = await (await client.get("/health")).json()
        assert "silent" in body["problem"]

        # Bounded fail-and-restart: the leader fatal-exits and the fail
        # marker releases live followers blocked in collectives.
        deadline = time.monotonic() + 5.0
        while not exits and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert exits == [1]
        assert store.get(distributed._fail_key(channel.epoch)) is not None
        resp.close()
    finally:
        follower.stop()
        await client.close()


async def test_drain_relay_completes_stream_before_any_member_exits(
    monkeypatch,
):
    """ISSUE acceptance: follower SIGTERM during an in-flight stream
    relays drain to the leader; the stream completes (and the leader
    publishes shutdown through the normal step path) before any member
    exits — fatal_exit is never called."""
    exits = []
    monkeypatch.setattr(distributed, "fatal_exit", exits.append)
    published = []
    monkeypatch.setattr(
        distributed, "broadcast_pyobj",
        lambda obj, is_source: published.append(obj),
    )
    store = LocalAckStore()
    engine, channel = _tiny_leader_engine(store, member_timeout_s=5.0)
    follower = _FakeFollower(store, channel)
    follower.start()
    app, server, client = await _start_engine_app(engine)
    try:
        stream = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "drain me gently",
                  "max_tokens": 200, "ignore_eos": True, "stream": True},
        )
        assert stream.status == 200
        await stream.content.readany()

        # The follower's SIGTERM path: relay through the side channel
        # (api_server._run_follower wires SIGTERM/POST /drain to this).
        fchan = LockstepChannel(
            DistributedEnv("x:1", 2, 1), member_timeout_s=5.0,
            ack_store=store,
        )
        fchan.epoch = channel.epoch
        fchan._epoch_adopted = True
        assert fchan.relay_drain()

        # The monitor picks the relay up at its next poll (member timeout
        # / 8) and begins the LEADER's drain: wait for that event, not for
        # the stream, which a warm compile cache ends within one poll.
        drain = app["drain"]
        for _ in range(200):
            if drain.draining:
                break
            await asyncio.sleep(0.05)
        assert drain.draining
        # The stream still runs to [DONE].
        body = await stream.content.read()
        assert b"[DONE]" in body
        assert await drain.wait(timeout=10.0) is True

        # New data-plane work is refused while the group exits.
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "late", "max_tokens": 2},
        )
        assert resp.status == 503
        assert exits == [], "a member exited before the stream completed"
        assert engine.slice_monitor.drain_relays == 1
        text = await (await client.get("/metrics")).text()
        assert "tpu:slice_drain_relays_total 1.0" in text
        assert f"tpu:lockstep_group_epoch {float(channel.epoch)}" in text
    finally:
        follower.stop()
        await client.close()
    # close() ran via the app lifecycle: the step loop's final publish
    # is the shutdown that releases followers to exit 0 in order.
    assert published and published[-1].shutdown is True
    assert exits == []
