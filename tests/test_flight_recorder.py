"""Window flight recorder + XLA compile tracking (PR 17).

Three layers, mirroring the subsystem's contract surface:

- jax-free units for obs/flight_recorder.py (ring bounds, exactly-once
  publication, attribution telescoping) and obs/compile_tracker.py
  (cache-growth detection, disabled-identity wrap).
- the REAL JAX engine on CPU: every dispatched window appears exactly
  once at /debug/windows with composition + accounting; per-window
  attribution sums to the request's decode-phase wall time within 10%;
  compile events are counted per executable key cold and stay flat warm,
  with the first-response compile marker riding the wire.
- the fake engine's jax-free mirrors of the same endpoints and metric
  families (what router CI integrates against).
"""

import time

from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.obs.compile_tracker import (
    CompileTracker,
    _TrackedJit,
    arg_signature,
)
from production_stack_tpu.obs.flight_recorder import (
    WINDOW_KINDS,
    FlightRecorder,
)

# -- recorder units (jax-free) ---------------------------------------------


def test_recorder_disabled_is_stateless():
    rec = FlightRecorder(enabled=False)
    assert rec.on_dispatch("decode", k=4, rows=2) is None
    rec.on_collect(None)  # the gated call sites pass the None through
    assert rec.snapshot() == []
    assert rec.windows_recorded == 0
    assert rec.dropped == 0


def test_recorder_publishes_exactly_once_with_composition():
    rec = FlightRecorder()
    r = rec.on_dispatch(
        "mixed", k=8, rows=2, seq_ids=("a", "b"), chain_depth=1,
        provisional=True, chunk_prompts=2, chunk_tokens_planned=48,
        fallback=None, host_gap_s=0.001, transfer_overlap_s=0.002,
        now=100.0,
    )
    assert r is not None and rec.snapshot() == []  # not visible pre-collect
    rec.on_collect(
        r, now=100.5, host_s=0.01, tokens_emitted=16, tokens_delivered=14,
        tokens_wasted=2, chunk_tokens_delivered=48,
    )
    snap = rec.snapshot()
    assert len(snap) == 1 and rec.windows_recorded == 1
    d = snap[0]
    assert d["kind"] in WINDOW_KINDS
    assert d["k"] == 8 and d["rows"] == 2 and d["seq_ids"] == ["a", "b"]
    assert d["chain_depth"] == 1 and d["provisional"] is True
    assert d["chunk_prompts"] == 2 and d["chunk_tokens_planned"] == 48
    assert d["chunk_tokens_delivered"] == 48
    assert d["tokens_emitted"] == 16 and d["tokens_wasted"] == 2
    assert d["transfer_overlap_s"] == 0.002
    assert d["attributed_s"] == 0.5


def test_recorder_attribution_telescopes_under_overlap():
    """The depth-2 lookahead pipeline overlaps dispatch intervals; raw
    (collect - dispatch) would double-count.  FIFO collects telescope:
    attributed = collect - max(dispatch, previous collect), so the sum
    recovers non-overlapped wall time exactly."""
    rec = FlightRecorder()
    r1 = rec.on_dispatch("decode", k=8, rows=1, now=100.0)
    r2 = rec.on_dispatch("decode", k=8, rows=1, provisional=True, now=100.4)
    rec.on_collect(r1, now=101.0)
    rec.on_collect(r2, now=101.3)
    by_id = {d["window_id"]: d for d in rec.snapshot()}
    assert by_id[r1.window_id]["attributed_s"] == 1.0
    # r2 in flight since 100.4 but overlapped r1 until 101.0.
    assert abs(by_id[r2.window_id]["attributed_s"] - 0.3) < 1e-9
    total = sum(d["attributed_s"] for d in by_id.values())
    assert abs(total - (101.3 - 100.0)) < 1e-9


def test_recorder_ring_bound_counts_drops_and_filters():
    rec = FlightRecorder(ring_size=4)
    for i in range(6):
        r = rec.on_dispatch(
            "decode", k=1, rows=1, seq_ids=(f"s{i % 2}",), now=float(i),
        )
        rec.on_collect(r, now=float(i) + 0.5)
    assert rec.windows_recorded == 6
    assert rec.dropped == 2
    snap = rec.snapshot()
    assert len(snap) == 4
    ids = [d["window_id"] for d in snap]
    assert ids == sorted(ids, reverse=True)  # newest first, no duplicates
    only_s1 = rec.snapshot(seq="s1")
    assert only_s1 and all(d["seq_ids"] == ["s1"] for d in only_s1)
    # for_request returns timeline (oldest-first) order.
    timeline = rec.for_request("s1")
    assert [d["window_id"] for d in timeline] == sorted(
        d["window_id"] for d in timeline
    )


# -- compile-tracker units (jax-free) --------------------------------------


class _FakeJit:
    """Duck-typed jit callable: cache grows on first call per distinct
    arg shape, like a real jax.jit executable cache."""

    def __init__(self):
        self._shapes = set()

    def _cache_size(self):
        return len(self._shapes)

    def __call__(self, n):
        self._shapes.add(n)
        return n * 2


def test_tracker_wrap_detects_cache_growth_and_keys_executables():
    tracker = CompileTracker()
    fn = tracker.wrap("decode_fn", _FakeJit())
    assert isinstance(fn, _TrackedJit)
    assert fn(4) == 8       # cold: cache grew -> compile event
    assert fn(4) == 8       # warm: no growth -> no event
    assert fn(8) == 16      # new shape: second compile
    assert tracker.compiled_shapes() == 2
    keys = set(tracker.seconds_by_executable())
    assert keys == {"decode_fn[4]", "decode_fn[8]"}
    # Events drain once (the engine tags owning windows after dispatch).
    events = tracker.drain_events()
    assert [e["executable"] for e in events] == ["decode_fn[4]", "decode_fn[8]"]
    assert tracker.drain_events() == []
    rows = tracker.snapshot()
    assert all(r["count"] == 1 and r["seconds"] >= 0.0 for r in rows)


def test_count_kernels_lowers_again_from_shapes():
    """The TPU-only leg of the tracker (how many Pallas kernels the
    program just compiled holds), on the two argument kinds that broke it
    on the chip: a donated (deleted) buffer, and an uncommitted scalar
    beside mesh-sharded arrays.  No kernels on the CPU: zero, not None."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from production_stack_tpu.obs.compile_tracker import count_kernels

    donating = jax.jit(lambda x, n: x * n, donate_argnums=(0,))
    x = jnp.ones((8, 8))
    donating(x, jnp.int32(2))
    assert x.is_deleted()
    assert count_kernels(donating, (x, jnp.int32(2)), {}) == 0

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("tp",))
    w = jax.device_put(jnp.ones((8, 8)), NamedSharding(mesh, P("tp", None)))
    sharded = jax.jit(lambda w, n: w * n)
    sharded(w, jnp.int32(3))  # the scalar is uncommitted, on device 0
    assert count_kernels(sharded, (w,), {"n": jnp.int32(3)}) == 0
    # A callable that cannot be lowered again is None, never an exception.
    assert count_kernels(_FakeJit(), (4,), {}) is None


def test_tracker_disabled_wrap_is_identity():
    tracker = CompileTracker(enabled=False)
    fn = _FakeJit()
    assert tracker.wrap("decode_fn", fn) is fn  # byte-identical fast path
    assert tracker.wrap("decode_fn", None) is None
    assert tracker.drain_events() == []


def test_tracker_passthrough_without_cache_probe():
    """A callable without _cache_size (older jax, plain function) must
    still be callable through the proxy — degrade, don't crash."""
    tracker = CompileTracker()
    fn = tracker.wrap("sample_fn", lambda x: x + 1)
    assert fn(41) == 42
    assert tracker.compiled_shapes() == 0


def test_arg_signature_is_compact_and_bounded():
    class _Arr:
        shape = (4, 128)
        dtype = "int32"

    sig = arg_signature((_Arr(), {"w": 1}, 7, True), {"k": 8})
    assert sig == "int32[4,128],params,7,True,k=8"
    long = arg_signature(tuple(range(100)), {})
    assert len(long) <= 96


# -- real JAX engine (CPU) -------------------------------------------------


def _small_config(**extra):
    from production_stack_tpu.engine.config import config_from_preset

    return config_from_preset(
        "tiny-llama",
        **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
           "scheduler.prefill_buckets": (16, 32), **extra},
    )


def test_every_dispatch_appears_exactly_once_real_engine():
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    eng = LLMEngine(_small_config())
    for i in range(2):
        eng.add_request(
            f"r{i}", prompt_token_ids=[3 + i, 5, 7, 11],
            sampling_params=SamplingParams(max_tokens=8, ignore_eos=True),
        )
    while eng.has_unfinished():
        eng.step()
    rec = eng.obs.recorder
    # Exactly once: every on_dispatch stamp got exactly one on_collect.
    assert rec.windows_recorded == rec._next_id > 0
    assert rec.dropped == 0
    snap = rec.snapshot()
    ids = [d["window_id"] for d in snap]
    assert len(ids) == len(set(ids)) == rec.windows_recorded
    for d in snap:
        assert d["kind"] in WINDOW_KINDS
        assert d["k"] >= 1
        assert d["collected_at"] is not None
        assert d["collected_at"] >= d["dispatched_at"]
        assert d["tokens_emitted"] >= d["tokens_delivered"] >= 0
    # Both requests rode at least one window each.
    for rid in ("r0", "r1"):
        assert rec.for_request(rid)


def test_window_attribution_sums_to_decode_wall_real_engine():
    """Acceptance gate: summing a request's per-window attributed_s
    recovers its decode-phase wall time within 10%."""
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    eng = LLMEngine(_small_config())
    eng.add_request(
        "attr0", prompt_token_ids=[3, 5, 7, 11],
        sampling_params=SamplingParams(max_tokens=48, ignore_eos=True),
    )
    t_first = t_end = None
    while eng.has_unfinished():
        for _out in eng.step():
            if t_first is None:
                t_first = time.time()  # first token == prefill collected
            t_end = time.time()
    decode_wall = t_end - t_first
    windows = eng.obs.recorder.for_request("attr0")
    assert windows
    win_sum = sum(
        w["attributed_s"] for w in windows if w["kind"] != "prefill"
    )
    assert abs(win_sum - decode_wall) <= 0.10 * decode_wall


async def test_compile_tracking_cold_then_warm_over_http():
    """Cold request: compile events counted per executable key, the
    response carries the compile marker, /debug/compiles reports the
    coverage join.  Warm same-shape request: counters flat, no marker."""
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    engine = AsyncEngine(_small_config())
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    client = TestClient(server)
    try:
        body = {"model": "tiny-llama", "prompt": "hi", "max_tokens": 4,
                "ignore_eos": True}
        cold = await client.post(
            "/v1/completions", json=body,
            headers={"x-request-id": "cold-1"},
        )
        assert cold.status == 200
        cold_body = await cold.json()
        assert cold_body.get("compile") is True  # marker on the wire
        tracker = engine.engine.obs.compile_tracker
        shapes_cold = tracker.compiled_shapes()
        assert shapes_cold > 0 and tracker.compile_seconds() > 0.0
        # Second identical request still compiles one prefill variant (a
        # prefix-cache hit runs the cached_len>0 path cold) — by the
        # third, every variant this workload touches is compiled.
        await client.post("/v1/completions", json=body,
                          headers={"x-request-id": "settle-1"})
        shapes_settled = tracker.compiled_shapes()
        seconds_settled = tracker.compile_seconds()
        warm = await client.post(
            "/v1/completions", json=body,
            headers={"x-request-id": "warm-1"},
        )
        assert warm.status == 200
        warm_body = await warm.json()
        assert "compile" not in warm_body
        assert tracker.compiled_shapes() == shapes_settled
        assert tracker.compile_seconds() == seconds_settled

        # The cold request's windows are compile-tainted in the join.
        joined = await (await client.get("/debug/requests/cold-1")).json()
        assert any(w.get("compile") for w in joined["windows"])
        assert sum(w.get("compile_s", 0.0) for w in joined["windows"]) > 0.0

        # /debug/windows: ring endpoint + ?seq= filter.
        wins = await (await client.get("/debug/windows")).json()
        assert wins["enabled"] is True and wins["windows"]
        ids = [w["window_id"] for w in wins["windows"]]
        assert len(ids) == len(set(ids))
        only = await (
            await client.get("/debug/windows", params={"seq": "warm-1"})
        ).json()
        assert only["windows"]
        assert all("warm-1" in w["seq_ids"] for w in only["windows"])

        # /debug/compiles: per-executable rows + warmup coverage report.
        comp = await (await client.get("/debug/compiles")).json()
        assert comp["enabled"] is True
        assert comp["compiled_shapes"] == shapes_settled
        for row in comp["executables"]:
            assert row["count"] >= 1 and row["seconds"] >= 0.0
            assert "[" in row["executable"]
        assert comp["coverage"]
        for fam, cov in comp["coverage"].items():
            assert cov["compiled"] >= 0 and cov["expected"] >= 0, fam
        compiled_fams = {
            r["executable"].split("[", 1)[0] for r in comp["executables"]
        }
        assert compiled_fams & set(comp["coverage"])

        # Metric families on the real scrape surface.
        metrics = await (await client.get("/metrics")).text()
        assert "# TYPE tpu:compile_seconds_total counter" in metrics
        assert 'tpu:compile_seconds_total{executable="' in metrics
        assert "tpu:compiled_shapes" in metrics
        assert "tpu:obs_trace_dropped_total" in metrics
    finally:
        await client.close()


def test_planner_decline_reasons_stamped():
    """A window the planner declines carries the reason on its K=1
    record: a waiting prefill forces single-step (waiting_head)."""
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    eng = LLMEngine(_small_config())
    eng.add_request(
        "w0", prompt_token_ids=[3, 5, 7, 11],
        sampling_params=SamplingParams(max_tokens=24, ignore_eos=True),
    )
    eng.step()  # prefill w0 -> decode rows exist
    # A newly waiting request makes the planner decline multi-step.
    eng.add_request(
        "w1", prompt_token_ids=[4, 6, 8, 10],
        sampling_params=SamplingParams(max_tokens=24, ignore_eos=True),
    )
    while eng.has_unfinished():
        eng.step()
    fallbacks = {
        w.get("fallback")
        for w in eng.obs.recorder.snapshot()
        if w.get("fallback")
    }
    from production_stack_tpu.router.stats.vocabulary import (
        TPU_MULTISTEP_FALLBACK_REASONS,
    )
    assert fallbacks <= set(TPU_MULTISTEP_FALLBACK_REASONS)


# -- fake-engine mirrors (jax-free, router-CI surface) ---------------------


async def test_fake_engine_mirrors_windows_compiles_and_marker():
    from production_stack_tpu.testing.fake_engine import (
        FakeEngineState,
        build_fake_engine_app,
    )

    state = FakeEngineState(
        tokens_per_sec=500.0, ttft=0.01, simulate_compiles=True,
    )
    server = TestServer(build_fake_engine_app(state))
    await server.start_server()
    client = TestClient(server)
    try:
        body = {"model": state.model, "prompt": "compile probe",
                "max_tokens": 3, "stream": True}
        resp = await client.post(
            "/v1/completions", json=body,
            headers={"x-request-id": "fk-cold"},
        )
        first = None
        async for chunk in resp.content.iter_any():
            if first is None:
                first = chunk
        assert first is not None and b'"compile": true' in first
        # Warm repeat (same prompt -> fully prefix-cached): no marker.
        resp = await client.post(
            "/v1/completions", json={**body, "stream": False},
            headers={"x-request-id": "fk-warm"},
        )
        warm_body = await resp.json()
        assert "compile" not in warm_body

        wins = await (await client.get("/debug/windows")).json()
        assert wins["enabled"] is True
        assert wins["recorded"] == 2  # one simulated window per request
        only = await (
            await client.get("/debug/windows", params={"seq": "fk-cold"})
        ).json()
        assert len(only["windows"]) == 1
        assert only["windows"][0]["seq_ids"] == ["fk-cold"]
        assert only["windows"][0]["tokens_delivered"] == 3

        comp = await (await client.get("/debug/compiles")).json()
        assert comp["enabled"] is True and comp["compiled_shapes"] == 1
        assert comp["executables"][0]["executable"].startswith("prefill_fn[")
        assert comp["coverage"]["prefill_fn"]["compiled"] == 1

        joined = await (await client.get("/debug/requests/fk-cold")).json()
        assert len(joined["windows"]) == 1
        assert joined["windows"][0].get("compile") is True

        metrics = await (await client.get("/metrics")).text()
        assert "# TYPE tpu:compile_seconds_total counter" in metrics
        assert 'tpu:compile_seconds_total{executable="prefill_fn[' in metrics
        assert "tpu:compiled_shapes 1" in metrics
        assert "tpu:obs_trace_dropped_total 0" in metrics
    finally:
        await client.close()


async def test_fake_engine_obs_off_keeps_new_surfaces_dark():
    """tracing disabled: no records, no compile events, endpoints report
    disabled — the same zero-state contract the real engine keeps."""
    from production_stack_tpu.testing.fake_engine import (
        FakeEngineState,
        build_fake_engine_app,
    )

    state = FakeEngineState(
        tokens_per_sec=500.0, ttft=0.0, tracing=False,
        simulate_compiles=True,
    )
    server = TestServer(build_fake_engine_app(state))
    await server.start_server()
    client = TestClient(server)
    try:
        resp = await client.post(
            "/v1/completions",
            json={"model": state.model, "prompt": "dark probe",
                  "max_tokens": 2},
            headers={"x-request-id": "dark-1"},
        )
        body = await resp.json()
        assert "compile" not in body
        wins = await (await client.get("/debug/windows")).json()
        assert wins["enabled"] is False and wins["windows"] == []
        comp = await (await client.get("/debug/compiles")).json()
        assert comp["enabled"] is False and comp["compiled_shapes"] == 0
    finally:
        await client.close()
