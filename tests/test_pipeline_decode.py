"""Async one-step-lookahead decode pipeline (SchedulerConfig.pipeline_decode).

Decode step N+1 is dispatched while step N's sampled tokens are still in
flight on the device, so greedy token streams must be byte-identical to
classic synchronous stepping — including when a sequence finishes
mid-flight (EOS/stop-token, which the provisional plan cannot predict)
and the engine must roll the in-flight successor's row back as a
discarded overrun.
"""

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import FinishReason, SamplingParams


def make_engine(pipeline, **sched_kw):
    sched = dict(
        max_num_seqs=4,
        prefill_buckets=(16, 32, 64),
        max_model_len=128,
        pipeline_decode=pipeline,
        # This file exercises the SINGLE-STEP lookahead pipeline; K-step
        # windows (the new default, which chain through the same
        # pipeline) are covered in tests/test_multistep_window.py.
        multi_step_window=False,
    )
    sched.update(sched_kw)
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(**sched),
    ))


def drain(engine, requests):
    """requests: [(id, prompt, SamplingParams)]; returns ({id: tokens},
    {id: finish_reason})."""
    for rid, prompt, sp in requests:
        engine.add_request(rid, prompt=prompt, sampling_params=sp)
    outs, finish = {}, {}
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 500, "engine failed to drain"
        for out in engine.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
            if out.finished:
                finish[out.seq_id] = out.finish_reason
    return outs, finish


def test_pipeline_enabled_by_default_and_engages():
    engine = make_engine(None)  # auto: single-step non-speculative -> on
    assert engine._pipeline_enabled
    lookaheads = []
    orig = engine._dispatch_decode_async

    def spy(seqs, lookahead, prev_sampled=None):
        lookaheads.append(lookahead)
        return orig(seqs, lookahead, prev_sampled)

    engine._dispatch_decode_async = spy
    outs, _ = drain(engine, [
        ("a", "steady state pipelining", SamplingParams(max_tokens=16)),
    ])
    assert len(outs["a"]) == 16
    # Steady state must ride the lookahead (delta-transfer) path, not
    # rebuild the batch every step.
    assert sum(lookaheads) >= 10


def test_greedy_parity_with_sync_path():
    reqs = [
        ("a", "the quick brown fox", SamplingParams(max_tokens=21)),
        ("b", "pack my box with", SamplingParams(max_tokens=13)),
        ("c", "five dozen jugs", SamplingParams(max_tokens=17)),
    ]
    ref, ref_fin = drain(make_engine(False), reqs)
    piped, piped_fin = drain(make_engine(True), reqs)
    assert ref == piped
    assert ref_fin == piped_fin


def test_parity_under_continuous_batching():
    """A request arriving mid-decode forces a pipeline break (admission),
    a sync prefill, and a batch rebuild; streams must stay identical."""
    def run(pipeline):
        engine = make_engine(pipeline)
        engine.add_request("a", prompt="first request",
                           sampling_params=SamplingParams(max_tokens=17))
        outs = {}
        fired = False
        steps = 0
        while engine.has_unfinished():
            steps += 1
            assert steps < 500
            for out in engine.step():
                outs.setdefault(out.seq_id, []).append(out.new_token_id)
            if not fired and len(outs.get("a", [])) >= 3:
                engine.add_request("b", prompt="second arrives later",
                                   sampling_params=SamplingParams(max_tokens=17))
                fired = True
        return outs

    assert run(False) == run(True)


def test_mid_flight_finish_rolls_back_provisional_plan():
    """A stop_token_ids finish is invisible to the provisional planner
    (unlike max_tokens it is not host-predictable), so the successor
    step is already in flight when the finish lands: its row must be
    discarded and the other sequences' streams must be unaffected."""
    reqs = [
        ("a", "the quick brown fox", SamplingParams(max_tokens=24)),
        ("b", "pack my box with", SamplingParams(max_tokens=24)),
    ]
    ref, _ = drain(make_engine(False), reqs)
    # Stop "a" via the token it would greedily emit at step 9: the finish
    # happens mid-pipeline with a's row still in the in-flight successor.
    stop_tok = ref["a"][9]
    stopped_reqs = [
        ("a", "the quick brown fox", SamplingParams(
            max_tokens=24, stop_token_ids=[stop_tok])),
        ("b", "pack my box with", SamplingParams(max_tokens=24)),
    ]
    ref_stop, ref_fin = drain(make_engine(False), stopped_reqs)
    piped_stop, piped_fin = drain(make_engine(True), stopped_reqs)
    assert piped_stop == ref_stop
    assert piped_fin == ref_fin
    assert piped_fin["a"] == FinishReason.STOP
    # The stop token is a sentinel event, never part of the stream.
    assert piped_stop["a"][-1] == -1

    # Nothing is left wedged in the pipeline and the survivor ran to its
    # full budget.
    assert len(piped_stop["b"]) == 24


def test_host_state_batches_fall_back_per_step():
    """Penalty/logprob batches must drop to the sync path (host-visible
    per-token state), and mixed batches still finish correctly."""
    engine = make_engine(True)
    outs, _ = drain(engine, [
        ("pen", "repeat repeat repeat", SamplingParams(
            max_tokens=9, presence_penalty=0.5)),
        ("plain", "other request", SamplingParams(max_tokens=9)),
    ])
    assert len(outs["pen"]) == 9
    assert len(outs["plain"]) == 9


def test_sampled_parity_with_sync_path():
    """Seeded temperature sampling matches the sync path while the batch
    is steady (no mid-stream admissions): the pipelined sampler consumes
    the same per-step PRNG key ordinal and per-row fold.  An admission
    landing mid-pipeline may shift key ordinals vs sync — only greedy
    parity is guaranteed across arbitrary event timings (docs/engine.md)."""
    reqs = [
        ("s", "stochastic stream", SamplingParams(
            max_tokens=12, temperature=0.9, top_p=0.9, seed=7)),
    ]
    ref, _ = drain(make_engine(False), reqs)
    piped, _ = drain(make_engine(True), reqs)
    assert ref == piped


def test_prefix_cache_not_polluted_by_overrun():
    """The discarded overrun token of a mid-flight finish writes KV past
    the kept sequence; those slots must never enter the prefix cache
    (full-block registration boundary)."""
    engine = make_engine(True)
    sp = SamplingParams(max_tokens=5)
    first, _ = drain(engine, [("a", "shared prefix prompt", sp)])
    second, _ = drain(engine, [("b", "shared prefix prompt", sp)])
    assert first["a"] == second["b"]
    ref, _ = drain(make_engine(False), [("r", "shared prefix prompt", sp)])
    assert second["b"] == ref["r"]


def test_preemption_parity_under_pool_pressure():
    """Preemption only runs with the pipeline drained (front dispatch);
    offload->restore under a tiny pool must still match the sync path."""
    prompts = ["alpha bravo charlie forever", "delta echo foxtrot forevers"]

    def run(pipeline, num_blocks):
        engine = LLMEngine(EngineConfig(
            model=ModelConfig(dtype="float32"),
            cache=CacheConfig(block_size=4, num_blocks=num_blocks,
                              host_offload_gb=0.25),
            scheduler=SchedulerConfig(
                max_num_seqs=2, prefill_buckets=(16, 32, 64),
                max_model_len=128, pipeline_decode=pipeline),
        ))
        reqs = [(f"r{i}", p, SamplingParams(max_tokens=16))
                for i, p in enumerate(prompts)]
        outs, _ = drain(engine, reqs)
        return outs, engine

    ref, _ = run(False, 128)
    got, engine = run(True, 20)
    assert engine.scheduler.num_preemptions > 0
    assert got == ref


def test_pipeline_composes_with_speculation_and_chains_windows():
    # Speculation fuses INTO the window scan, and fused speculative
    # windows chain through the pipeline like any window; without the
    # window a drafter is refused, whatever the pipeline gate says.
    cfg = SchedulerConfig(pipeline_decode=True, speculative_ngram=3)
    assert cfg.pipeline_enabled and cfg.spec_window_enabled
    assert SchedulerConfig(speculative_ngram=3).pipeline_enabled
    with pytest.raises(ValueError):
        SchedulerConfig(pipeline_decode=True, speculative_ngram=3,
                        multi_step_window=False)
    assert SchedulerConfig(multi_step_window=False).pipeline_enabled
    # The multi-step<->pipeline mutual exclusion stays lifted: the
    # pipeline chains K-step windows (window N+1 dispatched off window
    # N's in-flight carry), so both auto-resolve on together.
    cfg = SchedulerConfig(pipeline_decode=True, decode_window=4)
    assert cfg.pipeline_enabled and cfg.window_steps == 4
    assert SchedulerConfig(decode_window=4).pipeline_enabled
    assert SchedulerConfig().pipeline_enabled
    assert not SchedulerConfig(pipeline_decode=False).pipeline_enabled


def test_host_gap_metric_zero_when_pipelined():
    def gap(pipeline):
        engine = make_engine(pipeline)
        outs, _ = drain(engine, [
            ("g", "gap measurement prompt", SamplingParams(max_tokens=20)),
        ])
        assert len(outs["g"]) == 20
        return engine.stats()["decode_host_gap_ms"]

    assert gap(True) == 0.0
    assert gap(False) > 0.0


def test_abort_mid_flight_discards_cleanly():
    """Aborting a sequence whose rows sit in uncollected in-flight steps
    must not corrupt the surviving sequences' streams."""
    ref_engine = make_engine(True)
    ref, _ = drain(ref_engine, [
        ("keep", "the quick brown fox", SamplingParams(max_tokens=20)),
    ])

    engine = make_engine(True)
    engine.add_request("keep", prompt="the quick brown fox",
                       sampling_params=SamplingParams(max_tokens=20))
    engine.add_request("dead", prompt="pack my box with",
                       sampling_params=SamplingParams(max_tokens=20))
    outs = {}
    aborted = False
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 500
        for out in engine.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
        if not aborted and len(outs.get("dead", [])) >= 5:
            engine.abort_request("dead")  # rows still in flight
            aborted = True
    assert aborted
    assert len(outs["keep"]) == 20
    # Batch composition never changes per-sequence greedy tokens.
    assert outs["keep"] == ref["keep"]
