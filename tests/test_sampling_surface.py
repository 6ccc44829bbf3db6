"""min_p / logit_bias / stop_token_ids (OpenAI + vLLM sampling surface).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import FinishReason, SamplingParams
from production_stack_tpu.engine.sampling import sample_tokens


def make_engine(n_steps=1):
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128,
            # n_steps=1 is the single-token reference; the default config
            # now windows decode, so the reference disables it explicitly
            # (same convention as tests/test_multistep_decode.py).
            **(
                {"decode_window": n_steps}
                if n_steps > 1 else {"multi_step_window": False}
            ),
        ),
    ))


def drain(engine, sp, rid="r"):
    engine.add_request(rid, prompt="sampling surface probe",
                       sampling_params=sp)
    tokens, finish = [], None
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 200
        for out in engine.step():
            if out.new_token_id >= 0:
                tokens.append(out.new_token_id)
            if out.finished:
                finish = out.finish_reason
    return tokens, finish


def test_min_p_masks_low_probability_tokens():
    # Two rows: one with min_p so high only the argmax survives -> equals
    # greedy even at temperature 1; one with min_p=0 as control.
    logits = jnp.asarray(np.random.RandomState(0).randn(2, 50), jnp.float32)
    out = sample_tokens(
        logits,
        temperature=jnp.asarray([1.0, 1.0]),
        top_p=jnp.asarray([1.0, 1.0]),
        top_k=jnp.asarray([0, 0], jnp.int32),
        step_key=jax.random.PRNGKey(0),
        seq_seeds=jnp.asarray([1, 2], jnp.int32),
        min_p=jnp.asarray([0.9999, 0.0]),
    )
    assert int(out[0]) == int(jnp.argmax(logits[0]))


def test_logit_bias_forces_and_bans_tokens():
    engine = make_engine()
    # Find the natural greedy first token, then ban it with -100: the
    # output must change; conversely +100 on a chosen token forces it.
    base, _ = drain(make_engine(), SamplingParams(max_tokens=1), "b")
    natural = base[0]
    forced_id = (natural + 7) % engine.config.model.vocab_size
    out, _ = drain(engine, SamplingParams(
        max_tokens=1, logit_bias={natural: -100.0, forced_id: 100.0}))
    assert out[0] == forced_id


def test_stop_token_ids_end_without_emitting():
    # Force a known token via logit_bias, and declare it a stop token:
    # generation must end with reason STOP and emit NOTHING.
    engine = make_engine()
    base, _ = drain(make_engine(), SamplingParams(max_tokens=1), "b")
    target = (base[0] + 3) % engine.config.model.vocab_size
    out, finish = drain(engine, SamplingParams(
        max_tokens=8,
        logit_bias={target: 100.0},
        stop_token_ids=[target],
    ))
    assert out == []
    assert finish == FinishReason.STOP


def test_min_p_greedy_unchanged_multistep():
    """min_p flows through the fused multi-step scan: greedy parity."""
    a, _ = drain(make_engine(1), SamplingParams(max_tokens=9, min_p=0.2))
    b, _ = drain(make_engine(4), SamplingParams(max_tokens=9, min_p=0.2))
    assert a == b


def test_logit_bias_falls_back_to_single_step():
    engine = make_engine(4)
    assert engine._window_fn is not None
    base, _ = drain(make_engine(4), SamplingParams(max_tokens=3), "b")
    banned = base[1]
    out, _ = drain(engine, SamplingParams(
        max_tokens=3, logit_bias={banned: -100.0}))
    assert banned not in out
    # The fallback is observable, never silent (ISSUE 8 satellite).
    assert engine.multistep_fallback.get("logit_bias", 0) > 0


async def test_stream_options_include_usage_conformance():
    """OpenAI stream_options semantics: without include_usage no chunk
    carries usage; with it, one extra final chunk (empty choices) does;
    stream_options without stream=true is a 400."""
    import aiohttp
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 256,
           "cache.num_blocks": 128},
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    url = f"http://127.0.0.1:{server.port}"

    async def stream_chunks(payload):
        chunks = []
        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"{url}/v1/chat/completions", json=payload
            ) as resp:
                assert resp.status == 200
                async for raw in resp.content:
                    line = raw.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        chunks.append(json.loads(line[6:]))
        return chunks

    base = {"model": "tiny-llama", "max_tokens": 4,
            "messages": [{"role": "user", "content": "hi"}], "stream": True}
    try:
        plain = await stream_chunks(base)
        assert plain and all("usage" not in c for c in plain)

        with_usage = await stream_chunks(
            {**base, "stream_options": {"include_usage": True}}
        )
        usage_chunks = [c for c in with_usage if "usage" in c]
        assert len(usage_chunks) == 1
        assert usage_chunks[0] is with_usage[-1]
        assert usage_chunks[0]["choices"] == []
        u = usage_chunks[0]["usage"]
        assert u["completion_tokens"] == 4
        assert u["total_tokens"] == u["prompt_tokens"] + 4
        # Content chunks still arrived before it.
        assert any(
            c["choices"] and c["choices"][0]["delta"].get("content")
            for c in with_usage[:-1]
        )

        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/chat/completions", json={
                **{k: v for k, v in base.items() if k != "stream"},
                "stream_options": {"include_usage": True},
            }) as resp:
                assert resp.status == 400
                body = await resp.json()
                assert "stream_options" in body["error"]["message"]
    finally:
        await server.close()
