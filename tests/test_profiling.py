"""On-demand device profiling endpoints (/start_profile, /stop_profile —
vLLM's profiling surface, TPU-native via jax.profiler traces)."""

import asyncio
import os
import threading
import time

import aiohttp
from aiohttp.test_utils import TestServer

from production_stack_tpu.engine.config import config_from_preset
from production_stack_tpu.engine.server.api_server import build_engine_app
from production_stack_tpu.engine.server.async_engine import AsyncEngine


async def test_profile_cycle_writes_trace(tmp_path):
    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 128,
           "cache.num_blocks": 64},
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    url = f"http://127.0.0.1:{server.port}"
    trace_dir = str(tmp_path / "trace")
    try:
        async with aiohttp.ClientSession() as session:
            # Stop without start -> 409.
            async with session.post(f"{url}/stop_profile") as resp:
                assert resp.status == 409
            async with session.post(f"{url}/start_profile",
                                    json={"trace_dir": trace_dir}) as resp:
                assert resp.status == 200
                assert (await resp.json())["trace_dir"] == trace_dir
            # Second start while running -> 409.
            async with session.post(f"{url}/start_profile") as resp:
                assert resp.status == 409
            # Serve a request INSIDE the trace window (the point of the
            # feature: capture production steps in situ).
            async with session.post(f"{url}/v1/completions", json={
                "model": "tiny-llama", "prompt": "profile me",
                "max_tokens": 4,
            }) as resp:
                assert resp.status == 200
            async with session.post(f"{url}/stop_profile") as resp:
                assert resp.status == 200
                assert (await resp.json())["stop_s"] >= 0.0
            # The session's bracket on the host's clock, for whoever lays
            # the flight records against the trace.
            async with session.get(f"{url}/debug/windows") as resp:
                profile = (await resp.json())["profile"]
            (s0, s1), (e0, e1) = (
                profile["start_unix_ns"], profile["stop_unix_ns"])
            assert s0 <= s1 <= e0 <= e1 <= time.time_ns()
        profiles = []
        for root, _dirs, files in os.walk(trace_dir):
            profiles.extend(f for f in files if f.endswith(".xplane.pb"))
        assert profiles, f"no xplane trace written under {trace_dir}"
    finally:
        await server.close()


async def test_stop_profile_does_not_block_a_concurrent_request(
        tmp_path, monkeypatch):
    """Writing the trace takes seconds on a chip: it runs on a worker
    thread, and the event loop serves other requests meanwhile."""
    import jax

    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 128,
           "cache.num_blocks": 64},
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    url = f"http://127.0.0.1:{server.port}"
    writing = threading.Event()
    release = threading.Event()
    stop_trace = jax.profiler.stop_trace

    def slow_stop_trace():
        writing.set()
        # Held until the other request has been answered (or the test has
        # failed): no clock decides the outcome.
        release.wait(60)
        stop_trace()

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop_trace)
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(
                    f"{url}/start_profile",
                    json={"trace_dir": str(tmp_path / "trace")}) as resp:
                assert resp.status == 200
            stopping = asyncio.ensure_future(
                session.post(f"{url}/stop_profile"))
            while not writing.is_set():
                await asyncio.sleep(0.01)
            assert not stopping.done()
            # stop_trace is still "writing": a completion is served whole.
            async with session.post(f"{url}/v1/completions", json={
                "model": "tiny-llama", "prompt": "while it writes",
                "max_tokens": 4,
            }) as resp:
                assert resp.status == 200
                assert (await resp.json())["usage"]["completion_tokens"] == 4
            assert not stopping.done()
            release.set()
            resp = await stopping
            assert resp.status == 200
            resp.release()
    finally:
        release.set()
        await server.close()
