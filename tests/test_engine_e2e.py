"""Engine end-to-end on the tiny model (CPU): generation determinism,
continuous batching, prefix-cache reuse, offload-preemption survival, and
the OpenAI server surface.
"""


import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams


def tiny_engine(**overrides) -> LLMEngine:
    cfg = EngineConfig(
        model=ModelConfig(),  # tiny-llama defaults (byte-vocab compatible)
        cache=CacheConfig(
            block_size=4,
            num_blocks=overrides.pop("num_blocks", 128),
            host_offload_gb=overrides.pop("host_offload_gb", 0.25),
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=overrides.pop("max_num_seqs", 4),
            prefill_buckets=(16, 32, 64, 128),
            max_model_len=256,
        ),
    )
    return LLMEngine(cfg)


def run_to_completion(engine, max_steps=500):
    outputs = {}
    for _ in range(max_steps):
        if not engine.has_unfinished():
            break
        for out in engine.step():
            outputs.setdefault(out.seq_id, []).append(out)
    assert not engine.has_unfinished(), "engine did not drain"
    return outputs


def test_single_request_generates():
    engine = tiny_engine()
    engine.add_request("r1", prompt="hello world", sampling_params=SamplingParams(max_tokens=8))
    outputs = run_to_completion(engine)
    events = outputs["r1"]
    assert len(events) == 8
    assert events[-1].finished
    assert all(0 <= e.new_token_id < engine.config.model.vocab_size for e in events)


def test_greedy_determinism():
    def generate():
        engine = tiny_engine()
        engine.add_request("r", prompt="determinism", sampling_params=SamplingParams(max_tokens=6))
        return [e.new_token_id for e in run_to_completion(engine)["r"]]

    assert generate() == generate()


def test_batched_requests_all_finish():
    engine = tiny_engine()
    for i in range(6):  # more than max_num_seqs=4 -> queueing
        engine.add_request(
            f"r{i}", prompt=f"prompt number {i}", sampling_params=SamplingParams(max_tokens=5)
        )
    outputs = run_to_completion(engine)
    assert len(outputs) == 6
    for i in range(6):
        assert outputs[f"r{i}"][-1].finished


def test_batching_does_not_change_greedy_output():
    """A sequence's greedy tokens must be identical alone vs batched
    (paged attention correctness under mixed batches)."""
    prompt = "the quick brown fox"

    engine = tiny_engine()
    engine.add_request("solo", prompt=prompt, sampling_params=SamplingParams(max_tokens=6))
    solo = [e.new_token_id for e in run_to_completion(engine)["solo"]]

    engine2 = tiny_engine()
    engine2.add_request("a", prompt=prompt, sampling_params=SamplingParams(max_tokens=6))
    engine2.add_request("b", prompt="completely different text here", sampling_params=SamplingParams(max_tokens=6))
    engine2.add_request("c", prompt="third one", sampling_params=SamplingParams(max_tokens=6))
    batched = [e.new_token_id for e in run_to_completion(engine2)["a"]]
    assert solo == batched


def test_prefix_cache_reuse_same_output():
    """Second identical prompt hits the prefix cache and still produces
    identical greedy output."""
    prompt = "shared system prompt " * 4  # long enough for full blocks
    engine = tiny_engine()
    engine.add_request("first", prompt=prompt, sampling_params=SamplingParams(max_tokens=5))
    first = [e.new_token_id for e in run_to_completion(engine)["first"]]
    assert engine.block_pool.prefix_hit_rate == 0.0

    engine.add_request("second", prompt=prompt, sampling_params=SamplingParams(max_tokens=5))
    second = [e.new_token_id for e in run_to_completion(engine)["second"]]
    assert second == first
    assert engine.block_pool.prefix_hit_rate > 0.0  # cache actually hit


def test_sampling_with_temperature_differs_by_seed():
    engine = tiny_engine()
    engine.add_request(
        "s1", prompt="random", sampling_params=SamplingParams(max_tokens=12, temperature=1.0, seed=1)
    )
    engine.add_request(
        "s2", prompt="random", sampling_params=SamplingParams(max_tokens=12, temperature=1.0, seed=2)
    )
    outputs = run_to_completion(engine)
    t1 = [e.new_token_id for e in outputs["s1"]]
    t2 = [e.new_token_id for e in outputs["s2"]]
    assert t1 != t2  # overwhelmingly likely with 12 tokens


def test_preemption_offload_restores_and_finishes():
    """Tiny pool forces preemption; offloaded sequences must restore from
    host DRAM and finish with correct-looking output."""
    engine = tiny_engine(num_blocks=32, max_num_seqs=3)
    for i in range(3):
        engine.add_request(
            f"r{i}",
            prompt=f"some fairly long prompt text {i} " * 2,
            sampling_params=SamplingParams(max_tokens=24),
        )
    outputs = run_to_completion(engine, max_steps=2000)
    assert len(outputs) == 3
    for i in range(3):
        assert outputs[f"r{i}"][-1].finished
    assert engine.scheduler.num_preemptions > 0  # the scenario actually triggered
    assert engine.offload.saves > 0


def test_preemption_preserves_greedy_output():
    """Offload->restore must not change greedy generation."""
    # 27 chars -> 28 tokens -> 7 blocks each (block_size=4): both prefills
    # fit in a 19-usable-block pool (14 used), but each needs 4 more blocks
    # during decode (44 tokens total) -> growth exhausts the pool -> the
    # younger sequence is preempted+offloaded mid-decode.
    prompts = ["alpha bravo charlie forever", "delta echo foxtrot forevers"]

    big = tiny_engine(num_blocks=128, max_num_seqs=2)
    for i, p in enumerate(prompts):
        big.add_request(f"r{i}", prompt=p, sampling_params=SamplingParams(max_tokens=16))
    ref = {k: [e.new_token_id for e in v] for k, v in run_to_completion(big).items()}

    small = tiny_engine(num_blocks=20, max_num_seqs=2)
    for i, p in enumerate(prompts):
        small.add_request(f"r{i}", prompt=p, sampling_params=SamplingParams(max_tokens=16))
    got = {k: [e.new_token_id for e in v] for k, v in run_to_completion(small, 2000).items()}
    assert small.scheduler.num_preemptions > 0
    assert got == ref


def test_stats_surface():
    engine = tiny_engine()
    engine.add_request("r", prompt="stats", sampling_params=SamplingParams(max_tokens=3))
    run_to_completion(engine)
    s = engine.stats()
    assert s["total_finished"] == 1
    assert s["total_generated_tokens"] == 3
    assert 0.0 <= s["hbm_kv_usage_perc"] <= 1.0


# -- OpenAI server surface --------------------------------------------------


async def test_api_server_end_to_end():
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    cfg = EngineConfig(
        model=ModelConfig(),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(max_num_seqs=4, prefill_buckets=(16, 32, 64), max_model_len=128),
    )
    engine = AsyncEngine(cfg)
    app = build_engine_app(engine, served_model="tiny-llama")
    server = TestServer(app)
    await server.start_server()
    client = TestClient(server)
    try:
        resp = await client.get("/v1/models")
        assert (await resp.json())["data"][0]["id"] == "tiny-llama"

        # Non-streaming completion.
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "hi", "max_tokens": 4},
        )
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert body["usage"]["completion_tokens"] == 4

        # Streaming chat completion.
        resp = await client.post(
            "/v1/chat/completions",
            json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "hello"}],
                "stream": True,
                "max_tokens": 4,
            },
        )
        assert resp.status == 200
        raw = await resp.read()
        assert raw.strip().endswith(b"data: [DONE]")

        # Metrics in the tpu: vocabulary.
        resp = await client.get("/metrics")
        text = await resp.text()
        assert "tpu:num_requests_running" in text
        assert "tpu:hbm_kv_usage_perc" in text
        assert "tpu:total_generated_tokens" in text
    finally:
        await client.close()


async def test_overlong_prompt_rejected_with_400():
    """An over-max_model_len prompt must 400 cleanly, not truncate an SSE
    stream mid-flight (ClientPayloadError at the client)."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 128,
           "cache.num_blocks": 64},
    )
    engine = AsyncEngine(config)
    client = TestClient(TestServer(build_engine_app(engine, "tiny-llama")))
    await client.start_server()
    try:
        resp = await client.post(
            "/v1/chat/completions",
            json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "word " * 400}],
                "stream": True,
                "max_tokens": 4,
            },
        )
        assert resp.status == 400
        payload = await resp.json()
        assert payload["error"]["code"] == "context_length_exceeded"
    finally:
        await client.close()


@pytest.mark.parametrize("argv", [
    ["--num-scheduler-steps", "4"],      # decode_window's old name
    ["--no-multi-prompt-window"],        # the single-head mixed planner
])
def test_removed_engine_flags_are_rejected_by_the_parser(argv, capsys):
    from production_stack_tpu.engine.server import api_server

    with pytest.raises(SystemExit) as exit_:
        api_server.main(["--model", "tiny-llama", *argv])
    assert exit_.value.code == 2
    assert "unrecognized arguments: " + argv[0] in capsys.readouterr().err
