"""The engine's batched encode lane (server/encode_batcher.py;
docs/engine.md "The encode lane") on the CPU tiny-llama preset:

* one [B, T] forward serves a multi-text request, equal to the serial
  per-text path (the --no-encode-lane fallback) within float32 rounding;
* REGRESSION PIN: encode work never touches the device off the step
  thread — every encode_batch dispatch runs on "engine-step-loop";
* the PR-5 overload contract on the encode surface: structured 429 +
  Retry-After against the encode-queue caps, 504 for an expired
  x-request-deadline, queued-expiry shed counted by the step thread;
* encode metrics families render at /metrics.
"""

import asyncio
import threading
import time

import aiohttp
import numpy as np
from aiohttp.test_utils import TestServer

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
    config_from_preset,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.server.api_server import build_engine_app
from production_stack_tpu.engine.server.async_engine import AsyncEngine


def tiny_engine(**sched):
    defaults = dict(
        max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128
    )
    defaults.update(sched)
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(**defaults),
    ))


async def _server(**overrides):
    cfg = {"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 256,
           "cache.num_blocks": 128}
    cfg.update(overrides)
    config = config_from_preset("tiny-llama", **cfg)
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    return server, engine


# -- one forward, equal to serial within float32 rounding --------------------


def test_encode_batch_matches_serial_embed():
    eng = tiny_engine()
    texts = ["the cat sat on the mat", "quarterly revenue grew 8%", "hi"]
    ids = [eng.tokenizer.encode(t) for t in texts]
    batched = eng.encode_batch(ids)
    for vec, token_ids in zip(batched, ids):
        # Same forward, different batching: vmap over the single-text
        # encode.  XLA compiles the [B, T] program with another summation
        # order than the [T] one, so the float32 vectors agree to a few
        # ulps (observed: one), not bit for bit.  Components of an
        # L2-normalized vector are at most 1, where one float32 ulp is
        # 6e-8: atol 1e-6 is ~16 of them, and a wrong row or a padding
        # leak is off by 1e-2 or more.
        np.testing.assert_allclose(
            np.asarray(vec), np.asarray(eng.embed(token_ids)),
            rtol=0, atol=1e-6,
        )
    # Only batched texts count (the serial embed path predates the
    # counter and bench's serial leg must read as zero lane traffic).
    assert eng.stats()["encode_texts_total"] == len(texts)
    assert "encode_batch_fn" in eng.compile_inventory()


def test_encode_batch_bucket_padding_invariant():
    eng = tiny_engine()
    ids = eng.tokenizer.encode("bucket invariance probe")
    alone = eng.encode_batch([ids])[0]
    # Padded into a B=4 bucket next to longer neighbors (different T
    # bucket too): pad rows and pad tokens must not leak into the vector.
    long_ids = eng.tokenizer.encode("a longer neighbor text, bigger bucket")
    packed = eng.encode_batch([ids, long_ids, ids])
    np.testing.assert_allclose(
        np.asarray(packed[0]), np.asarray(alone), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(packed[2]), np.asarray(alone), rtol=1e-5, atol=1e-6
    )


# -- the lane over HTTP ------------------------------------------------------


async def test_encode_runs_on_step_thread_and_batches_one_forward():
    server, engine = await _server()
    assert engine.encode_batcher is not None, "lane off by default?"
    eng = engine.engine
    seen_threads = []
    calls = []
    orig = eng.encode_batch

    def recording(batch_token_ids):
        seen_threads.append(threading.current_thread().name)
        calls.append(len(batch_token_ids))
        return orig(batch_token_ids)

    eng.encode_batch = recording
    url = f"http://127.0.0.1:{server.port}"
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/embeddings", json={
                "model": "tiny-llama",
                "input": ["first text", "second text", "third text"],
            }) as resp:
                assert resp.status == 200, await resp.text()
                body = await resp.json()
            async with session.get(f"{url}/metrics") as resp:
                metrics = await resp.text()
    finally:
        eng.encode_batch = orig
        await server.close()
    assert [d["index"] for d in body["data"]] == [0, 1, 2]
    # THE PIN: every device dispatch for encode work happened on the
    # step thread — never the event loop (the pre-lane serial path), and
    # the three texts rode ONE batched forward.
    assert seen_threads and set(seen_threads) == {"engine-step-loop"}
    assert calls == [3]
    for family in ("tpu:encode_texts_total", "tpu:encode_queue_depth",
                   "tpu:encode_batch_size", "tpu:encode_seconds"):
        assert family in metrics, family


async def test_encode_lane_off_serial_parity_bitexact():
    """--no-encode-lane keeps byte-identical answers (the A/B bench's
    parity leg): same forward either way, only the batching differs."""
    server_on, engine_on = await _server()
    server_off, engine_off = await _server(**{"scheduler.encode_lane": False})
    assert engine_off.encode_batcher is None
    texts = ["alpha doc", "a rather longer beta document to embed", "g"]
    try:
        async with aiohttp.ClientSession() as session:
            bodies = []
            for server in (server_on, server_off):
                url = f"http://127.0.0.1:{server.port}"
                async with session.post(f"{url}/v1/embeddings", json={
                    "model": "tiny-llama", "input": texts,
                }) as resp:
                    assert resp.status == 200
                    bodies.append(await resp.json())
    finally:
        await server_on.close()
        await server_off.close()
    assert bodies[0]["data"] == bodies[1]["data"]
    assert bodies[0]["usage"] == bodies[1]["usage"]


async def test_encode_admission_429_and_expired_deadline_504():
    server, engine = await _server(
        **{"scheduler.max_queued_encode_texts": 2}
    )
    url = f"http://127.0.0.1:{server.port}"
    try:
        async with aiohttp.ClientSession() as session:
            # More texts than the queue cap ever admits: structured 429
            # with Retry-After, counted like any engine shed.
            async with session.post(f"{url}/v1/embeddings", json={
                "model": "tiny-llama", "input": ["a", "b", "c"],
            }) as resp:
                assert resp.status == 429
                assert "Retry-After" in resp.headers
                err = (await resp.json())["error"]
                assert err["type"] == "overloaded"
                assert "encode lane" in err["message"]
            # An already-expired deadline sheds 504 BEFORE queueing.
            async with session.post(
                f"{url}/v1/embeddings",
                json={"model": "tiny-llama", "input": "too late"},
                headers={"x-request-deadline": str(time.time() - 5.0)},
            ) as resp:
                assert resp.status == 504
                assert (await resp.json())["error"]["type"] == \
                    "deadline_expired"
            # Within the cap: still served (the cap bounds the QUEUE,
            # not the lane).
            async with session.post(f"{url}/v1/embeddings", json={
                "model": "tiny-llama", "input": ["a", "b"],
            }) as resp:
                assert resp.status == 200
    finally:
        await server.close()
    assert engine.engine.admission_rejected >= 1
    assert engine.engine.deadline_expired_admission >= 1


async def test_rerank_and_score_ride_the_lane():
    """The whole encode surface (not just /v1/embeddings) goes through
    the batcher: one request's documents+query embed as one batch."""
    server, engine = await _server()
    eng = engine.engine
    calls = []
    orig = eng.encode_batch

    def recording(batch_token_ids):
        calls.append((threading.current_thread().name, len(batch_token_ids)))
        return orig(batch_token_ids)

    eng.encode_batch = recording
    url = f"http://127.0.0.1:{server.port}"
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/rerank", json={
                "model": "tiny-llama", "query": "which doc",
                "documents": ["doc one", "doc two", "doc three"],
            }) as resp:
                assert resp.status == 200, await resp.text()
                rerank = await resp.json()
            async with session.post(f"{url}/v1/score", json={
                "model": "tiny-llama", "text_1": "anchor",
                "text_2": ["left", "right"],
            }) as resp:
                assert resp.status == 200, await resp.text()
                score = await resp.json()
    finally:
        eng.encode_batch = orig
        await server.close()
    assert len(rerank["results"]) == 3
    assert len(score["data"]) == 2
    assert all(name == "engine-step-loop" for name, _ in calls)
    # rerank = query + 3 docs in one batch; score = 1 + 2 in one batch.
    assert sorted(n for _, n in calls) == [3, 4]


def test_batcher_shutdown_fails_queued_futures():
    """close() must resolve queued futures with an error instead of
    leaving awaiting handlers hung past the step thread's exit."""
    from production_stack_tpu.engine.server.encode_batcher import (
        EncodeBatcher,
    )

    eng = tiny_engine()
    batcher = EncodeBatcher(eng)

    async def run():
        loop = asyncio.get_running_loop()
        futures = batcher.submit([[1, 2, 3], [4, 5]], loop)
        assert eng.encode_queue_depth == 2
        batcher.fail_all(RuntimeError("engine shutting down"))
        assert eng.encode_queue_depth == 0
        for fut in futures:
            try:
                await fut
            except RuntimeError as e:
                assert "shutting down" in str(e)
            else:
                raise AssertionError("future resolved without error")

    asyncio.run(run())
