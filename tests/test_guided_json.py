"""Guided decoding: response_format json_object (engine/guided.py).

A random-weight tiny model has no idea what JSON is; if its constrained
output still parses, the automaton and the host-side candidate selection
are doing all the work — exactly what the test needs.
"""

import json

import aiohttp
import pytest
from aiohttp.test_utils import TestServer

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
    config_from_preset,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import FinishReason, SamplingParams
from production_stack_tpu.engine.guided import DONE, advance_bytes, initial_state
from production_stack_tpu.engine.server.api_server import build_engine_app
from production_stack_tpu.engine.server.async_engine import AsyncEngine


def make_engine(n_steps=1):
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=96),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=256,
            **({"decode_window": n_steps} if n_steps > 1 else {}),
        ),
    ))


def drain(engine, sp, rid="g"):
    engine.add_request(rid, prompt="produce json:", sampling_params=sp)
    tokens, finish = [], None
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 500
        for out in engine.step():
            if out.new_token_id >= 0:
                tokens.append(out.new_token_id)
            if out.finished:
                finish = out.finish_reason
    return tokens, finish


def decode_output(engine, tokens):
    return engine.tokenizer.decode(tokens)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_guided_output_parses_as_json_object(temperature):
    engine = make_engine()
    tokens, finish = drain(engine, SamplingParams(
        max_tokens=120, temperature=temperature, seed=3,
        response_format="json_object",
    ))
    text = decode_output(engine, tokens)
    obj = json.loads(text)  # must parse...
    assert isinstance(obj, dict)  # ...as an OBJECT (json_object contract)
    assert finish == FinishReason.STOP  # closed JSON forces EOS, not length


def test_guided_works_under_multistep_config():
    """Guided sequences force the single-step fallback; the engine must
    still drain correctly when configured with fused multi-step."""
    engine = make_engine(n_steps=4)
    tokens, _ = drain(engine, SamplingParams(
        max_tokens=80, response_format="json_object"))
    json.loads(decode_output(engine, tokens))


def test_small_budget_closes_minimal_object():
    """Budget-aware closing: with just enough budget the guide steers to
    the minimal '{}' instead of truncating mid-structure."""
    engine = make_engine()
    tokens, finish = drain(engine, SamplingParams(
        max_tokens=4, response_format="json_object"))
    assert json.loads(decode_output(engine, tokens)) == {}
    assert finish == FinishReason.STOP


def test_budget_below_minimum_is_bounded():
    """max_tokens=1 cannot fit any JSON object: generation must stop at
    LENGTH, never loop."""
    engine = make_engine()
    tokens, finish = drain(engine, SamplingParams(
        max_tokens=1, response_format="json_object"))
    assert len(tokens) <= 1
    assert finish == FinishReason.LENGTH


def test_every_prefix_is_automaton_valid():
    """Stronger than end-state parsing: every emitted token must keep the
    byte stream inside the automaton's language."""
    engine = make_engine()
    tokens, _ = drain(engine, SamplingParams(
        max_tokens=60, response_format="json_object"))
    state = initial_state(True)
    for t in tokens:
        piece = engine.tokenizer.decode([t]).encode()
        state = advance_bytes(state, piece)
        assert state is not None
    assert state.mode == DONE


def test_unknown_response_format_rejected():
    engine = make_engine()
    with pytest.raises(ValueError, match="response_format"):
        engine.add_request("x", prompt="p", sampling_params=SamplingParams(
            response_format="xml"))


async def test_response_format_through_api():
    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 256,
           "cache.num_blocks": 128},
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    url = f"http://127.0.0.1:{server.port}"
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/chat/completions", json={
                "model": "tiny-llama", "max_tokens": 120,
                "messages": [{"role": "user", "content": "emit json"}],
                "response_format": {"type": "json_object"},
            }) as resp:
                assert resp.status == 200
                body = await resp.json()
        content = body["choices"][0]["message"]["content"]
        assert isinstance(json.loads(content), dict)

        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "x"}],
                "response_format": {"type": "json_schema"},
            }) as resp:
                assert resp.status == 400
    finally:
        await server.close()


def test_guided_finish_revalidates_assembled_text(monkeypatch):
    """Per-token validation uses decode([id]), whose concatenation need
    not equal the assembled decode() for sentencepiece/byte-BPE vocabs;
    the finish-time re-check must surface the divergence as
    finish_reason=guided_invalid instead of returning non-JSON under a
    json_object contract (advisor r4 finding)."""
    engine = make_engine()
    orig_decode = engine.tokenizer.decode

    def corrupting_decode(ids, *args, **kwargs):
        # Single-token calls (TokenTextCache) see the real text; the
        # finish-time assembled decode sees a divergent string.
        if hasattr(ids, "__len__") and len(ids) > 1:
            return "not json {"
        return orig_decode(ids, *args, **kwargs)

    monkeypatch.setattr(engine.tokenizer, "decode", corrupting_decode)
    _, finish = drain(engine, SamplingParams(
        max_tokens=120, temperature=0.0, response_format="json_object",
    ))
    assert finish == FinishReason.GUIDED_INVALID
