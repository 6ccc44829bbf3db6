"""vLLM API parity: /tokenize + /detokenize endpoints and the min_tokens
sampling parameter (EOS/stop_token_ids suppressed until N generated)."""

import aiohttp
from aiohttp.test_utils import TestServer

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
    config_from_preset,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.server.api_server import build_engine_app
from production_stack_tpu.engine.server.async_engine import AsyncEngine


async def _server():
    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 256,
           "cache.num_blocks": 128},
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    return server, f"http://127.0.0.1:{server.port}"


async def test_tokenize_detokenize_roundtrip():
    server, url = await _server()
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/tokenize", json={
                "prompt": "hello tokenizer world",
            }) as resp:
                assert resp.status == 200
                body = await resp.json()
            assert body["count"] == len(body["tokens"]) > 0
            assert body["max_model_len"] == 256
            async with session.post(f"{url}/detokenize", json={
                "tokens": body["tokens"],
            }) as resp:
                assert resp.status == 200
                text = (await resp.json())["prompt"]
            assert "hello" in text and "world" in text

            # Chat-message form renders the chat template first.
            async with session.post(f"{url}/tokenize", json={
                "messages": [{"role": "user", "content": "hi"}],
            }) as resp:
                assert resp.status == 200
                chat_count = (await resp.json())["count"]
            assert chat_count > 0

            async with session.post(f"{url}/tokenize", json={}) as resp:
                assert resp.status == 400
            async with session.post(f"{url}/detokenize", json={
                "tokens": "nope",
            }) as resp:
                assert resp.status == 400
    finally:
        await server.close()


def _drain(engine, sp, rid="r", prompt="count to twenty"):
    engine.add_request(rid, prompt=prompt, sampling_params=sp)
    tokens = []
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 400
        for out in engine.step():
            if out.new_token_id >= 0:
                tokens.append(out.new_token_id)
    return tokens


def _engine(**sched):
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128,
            **sched,
        ),
    ))


def test_min_tokens_suppresses_early_stop_token():
    """A stop_token_id that would fire on step 1 must be suppressed until
    min_tokens is reached — then generation may stop on it."""
    engine = _engine()
    # Find what greedy emits first, then ban it as a stop token.
    first = _drain(_engine(), SamplingParams(max_tokens=1))[0]
    baseline = _drain(
        engine, SamplingParams(max_tokens=12, stop_token_ids=[first]),
        rid="base",
    )
    # Without min_tokens the stop fires immediately (no text tokens).
    assert baseline == []

    withmin = _drain(
        _engine(),
        SamplingParams(max_tokens=12, stop_token_ids=[first], min_tokens=5),
    )
    assert len(withmin) >= 5
    assert first not in withmin[:5]


def test_min_tokens_under_multistep_engine():
    """min_tokens drops the batch to single-step while unmet; output
    still honors the floor under a decode_window=4 engine."""
    tokens = _drain(
        _engine(decode_window=4),
        SamplingParams(max_tokens=10, min_tokens=10),
    )
    assert len(tokens) == 10


async def test_min_tokens_validation_through_server():
    server, url = await _server()
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/completions", json={
                "model": "tiny-llama", "prompt": "x",
                "max_tokens": 4, "min_tokens": 9,
            }) as resp:
                assert resp.status == 400
                assert "min_tokens" in (await resp.json())["error"]["message"]
            async with session.post(f"{url}/v1/completions", json={
                "model": "tiny-llama", "prompt": "x",
                "max_tokens": 6, "min_tokens": 6,
            }) as resp:
                assert resp.status == 200
                body = await resp.json()
            assert body["usage"]["completion_tokens"] == 6
    finally:
        await server.close()


async def test_tokenize_proxied_through_router():
    """The router proxies /tokenize and /detokenize to the model's
    engine like any model-bound request."""
    from aiohttp.test_utils import TestClient

    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.parser import parse_args

    engine_server, engine_url = await _server()
    app = build_app(parse_args([
        "--static-backends", engine_url,
        "--static-models", "tiny-llama",
        "--engine-stats-interval", "1",
    ]))
    router = TestServer(app)
    await router.start_server()
    client = TestClient(router)
    try:
        resp = await client.post("/tokenize", json={
            "model": "tiny-llama", "prompt": "router tokenize",
        })
        assert resp.status == 200
        body = await resp.json()
        assert body["count"] == len(body["tokens"]) > 0
        resp = await client.post("/detokenize", json={
            "model": "tiny-llama", "tokens": body["tokens"],
        })
        assert resp.status == 200
        assert "router" in (await resp.json())["prompt"]
    finally:
        await client.close()
        await engine_server.close()
