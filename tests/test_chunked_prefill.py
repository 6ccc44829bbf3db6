"""Chunked prefill: prompts longer than the largest prefill bucket are
split across multiple full-bucket steps instead of silently truncated
(the round-1 scheduler truncated to the largest bucket and decode then
attended to zero-filled KV for the tail — scheduler.py history).
"""

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.scheduler import Scheduler, SchedulerConfig as SC
from production_stack_tpu.engine.core.sequence import SamplingParams, Sequence
from production_stack_tpu.engine.kv.block_pool import BlockPool


def make_engine(buckets, max_model_len=256, **overrides):
    cfg = EngineConfig(
        model=ModelConfig(),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(
            max_num_seqs=overrides.pop("max_num_seqs", 4),
            prefill_buckets=buckets,
            max_model_len=max_model_len,
        ),
    )
    return LLMEngine(cfg)


def drain(engine, max_steps=500):
    outputs = {}
    for _ in range(max_steps):
        if not engine.has_unfinished():
            break
        for out in engine.step():
            outputs.setdefault(out.seq_id, []).append(out.new_token_id)
    assert not engine.has_unfinished(), "engine did not drain"
    return outputs


# ~180 byte-tokens: longer than the largest test bucket (64), within
# max_model_len=256 including generation headroom.
LONG_PROMPT = " ".join(f"token{i}" for i in range(24))


def test_scheduler_emits_chunked_plans():
    pool = BlockPool(num_blocks=128, block_size=4)
    sched = Scheduler(SC(max_num_seqs=2, prefill_buckets=(16, 32), max_model_len=256), pool)
    seq = Sequence("s", list(range(100)), SamplingParams())
    sched.add_seq(seq)

    plan1 = sched.schedule().prefill_chunk
    assert plan1 is not None and not plan1.is_final
    assert plan1.num_new_tokens == 32 and plan1.cached_len == 0
    assert seq.partial_prefill and sched.num_running == 0

    plan2 = sched.schedule().prefill_chunk
    assert not plan2.is_final
    assert plan2.cached_len == 32 and plan2.num_new_tokens == 32
    # Chunk 2 continues from chunk 1's blocks.
    assert plan2.prefix_block_ids == plan1.new_block_ids

    plan3 = sched.schedule().prefill_chunk
    assert not plan3.is_final and plan3.cached_len == 64

    plan4 = sched.schedule().prefill_chunk
    assert plan4.is_final
    assert plan4.cached_len == 96 and plan4.num_new_tokens == 4
    assert not seq.partial_prefill and sched.num_running == 1
    # Full block table covers the whole prompt.
    assert len(seq.block_table) == 100 // 4


def test_long_prompt_matches_single_shot_prefill():
    """Greedy output through chunked prefill == one-bucket prefill."""
    chunked = make_engine(buckets=(16, 32, 64))
    single = make_engine(buckets=(16, 32, 64, 256))
    for eng in (chunked, single):
        eng.add_request(
            "r", prompt=LONG_PROMPT, sampling_params=SamplingParams(max_tokens=8)
        )
    got = drain(chunked)["r"]
    want = drain(single)["r"]
    assert got == want


def test_long_prompt_prefix_cache_after_chunked_prefill():
    engine = make_engine(buckets=(16, 32, 64))
    engine.add_request("a", prompt=LONG_PROMPT, sampling_params=SamplingParams(max_tokens=4))
    first = drain(engine)["a"]
    hit_before = engine.block_pool.hit_tokens
    engine.add_request("b", prompt=LONG_PROMPT, sampling_params=SamplingParams(max_tokens=4))
    second = drain(engine)["b"]
    assert second == first
    assert engine.block_pool.hit_tokens > hit_before  # prefix reused


def test_chunked_prefill_interleaves_with_decode():
    engine = make_engine(buckets=(16, 32, 64), max_num_seqs=2)
    engine.add_request("short", prompt="hi", sampling_params=SamplingParams(max_tokens=20))
    # Let the short request enter decode first (its prefill emits token 1).
    outputs = {}
    for out in engine.step():
        outputs.setdefault(out.seq_id, []).append(out.new_token_id)
    engine.add_request(
        "long", prompt=LONG_PROMPT, sampling_params=SamplingParams(max_tokens=4)
    )
    for seq_id, toks in drain(engine).items():
        outputs.setdefault(seq_id, []).extend(toks)
    assert len(outputs["short"]) == 20
    assert len(outputs["long"]) == 4


# -- covers: a prompt under the largest bucket in several smaller programs --

# 600 byte-tokens: three 256-slot programs under (256, 2048), one program
# under (1024,).
COVERED_PROMPT = " ".join(f"word{i}" for i in range(88))[:600]


def make_cover_engine(buckets, **model):
    return LLMEngine(EngineConfig(
        model=ModelConfig(**model),
        cache=CacheConfig(block_size=16, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=buckets, max_model_len=1024,
        ),
    ))


def prefill_records(engine):
    windows = engine.obs.windows_payload()["windows"]
    return sorted(
        (w for w in windows if w["kind"] == "prefill"),
        key=lambda w: w["window_id"],
    )


@pytest.mark.parametrize("prompt_tokens", [600, 256, 257])
def test_covered_prompt_matches_single_bucket_prefill(prompt_tokens):
    """Greedy streams are equal whether a prompt runs as a cover of small
    programs or in one large one (257 tokens: the second chunk holds one)."""
    prompt = COVERED_PROMPT[: prompt_tokens - 1]  # + BOS
    streams, covers = [], []
    for buckets in ((256, 2048), (1024,)):
        engine = make_cover_engine(buckets)
        assert len(engine.tokenizer.encode(prompt)) == prompt_tokens
        engine.add_request(
            "r", prompt=prompt, sampling_params=SamplingParams(max_tokens=8)
        )
        streams.append(drain(engine)["r"])
        covers.append([w["cover"] for w in prefill_records(engine)])
    assert streams[0] == streams[1]
    n = -(-prompt_tokens // 256)
    # The record's ``cover``: this dispatch's bucket, then those to come.
    assert covers == [[[256] * k for k in range(n, 0, -1)], [[1024]]]


def test_prefix_hit_then_cover_matches_a_cold_engine():
    engine = make_cover_engine((256, 2048))
    shared, tail = COVERED_PROMPT[:320], COVERED_PROMPT[320:599]
    engine.add_request(
        "a", prompt=shared, sampling_params=SamplingParams(max_tokens=2)
    )
    drain(engine)
    hit_before = engine.block_pool.hit_tokens
    engine.add_request(
        "b", prompt=shared + tail, sampling_params=SamplingParams(max_tokens=8)
    )
    warm = drain(engine)["b"]
    assert engine.block_pool.hit_tokens - hit_before == 320
    # 280 new tokens behind the 320 cached: two chunks, the hit their prefix.
    recs = prefill_records(engine)[-2:]
    assert [w["cover"] for w in recs] == [[256, 256], [256]]
    assert [w["cached_tokens"] for w in recs] == [320, 576]
    assert [w["new_tokens"] for w in recs] == [256, 24]
    cold = make_cover_engine((1024,))
    cold.add_request(
        "b", prompt=shared + tail, sampling_params=SamplingParams(max_tokens=8)
    )
    assert drain(cold)["b"] == warm


def test_abort_between_two_chunks_of_a_cover_frees_its_blocks():
    engine = make_cover_engine((256, 2048))
    free = engine.block_pool.num_free_blocks
    engine.add_request(
        "r", prompt=COVERED_PROMPT, sampling_params=SamplingParams(max_tokens=8)
    )
    assert engine.step() == []  # first chunk: KV written, nothing sampled
    assert engine.block_pool.num_free_blocks == free - 16
    engine.abort_request("r")
    assert not engine.has_unfinished()
    assert engine.block_pool.num_free_blocks == free


def test_echo_logprobs_of_a_covered_prompt_match_the_single_bucket_ones():
    """echo+logprobs prompts run the prompt-logprobs prefill chunk by chunk
    from ``cached_len`` on: a cover changes no position's entry."""
    plps = []
    for buckets in ((256, 2048), (1024,)):
        engine = make_cover_engine(buckets, dtype="float32")
        engine.add_request("e", prompt=COVERED_PROMPT, sampling_params=SamplingParams(
            max_tokens=2, echo=True, logprobs=True, top_logprobs=2,
        ))
        plp = None
        while engine.has_unfinished():
            for out in engine.step():
                if out.prompt_logprobs is not None:
                    plp = out.prompt_logprobs
        plps.append(plp)
        assert len(prefill_records(engine)) == (3 if len(buckets) > 1 else 1)
    covered, single = plps
    assert len(covered) == len(single) == 601 and covered[0] == (None, None)
    for (lp, pairs), (ref_lp, ref_pairs) in zip(covered[1:], single[1:]):
        assert abs(lp - ref_lp) < 1e-4
        assert [t for t, _ in pairs] == [t for t, _ in ref_pairs]
