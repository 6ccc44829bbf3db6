"""Multi-step decode scheduling (the K-step decode window's basic
contract).

N decode iterations run as ONE
device dispatch (lax.scan with on-device sampling), so greedy outputs must
be bit-identical to classic single-token stepping, stop conditions must
truncate (now via the device stop-mask), and block allocation must cover
the whole budget.  The window-first surface (multi_step_window /
decode_window, on-device penalties, stop-mask internals) is covered in
tests/test_multistep_window.py.
"""


from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import FinishReason, SamplingParams


def make_engine(n_steps: int, **sched_kw):
    sched = dict(
        max_num_seqs=2,
        prefill_buckets=(16, 32, 64),
        max_model_len=128,
    )
    # n_steps=1 is the single-token reference: the default config now
    # windows decode (multi_step_window auto-on), so the reference must
    # disable it explicitly.
    if n_steps > 1:
        sched["decode_window"] = n_steps
    else:
        sched["multi_step_window"] = False
    sched.update(sched_kw)
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(**sched),
    ))


def drain(engine, requests):
    """requests: [(id, prompt, SamplingParams)]; returns {id: tokens}."""
    for rid, prompt, sp in requests:
        engine.add_request(rid, prompt=prompt, sampling_params=sp)
    outs = {}
    finish = {}
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 500, "engine failed to drain"
        for out in engine.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
            if out.finished:
                finish[out.seq_id] = out.finish_reason
    return outs, finish


def test_greedy_parity_with_single_step():
    reqs = [
        ("a", "the quick brown fox", SamplingParams(max_tokens=21)),
        ("b", "pack my box with", SamplingParams(max_tokens=13)),
    ]
    ref, ref_fin = drain(make_engine(1), reqs)
    multi, multi_fin = drain(make_engine(4), reqs)
    assert ref == multi
    assert ref_fin == multi_fin


def test_max_tokens_exact_and_length_reason():
    outs, finish = drain(
        make_engine(8),
        [("a", "hello world", SamplingParams(max_tokens=5))],
    )
    # 8-step budget overshoots a 5-token request; the host must truncate.
    assert len(outs["a"]) == 5
    assert finish["a"] == FinishReason.LENGTH


def test_budget_crosses_block_boundaries():
    # block_size=4 and 21 tokens: the scan writes KV across ~6 blocks that
    # must be pre-allocated by the scheduler, not one per step.
    outs, _ = drain(
        make_engine(7),
        [("a", "a b c d e f g h", SamplingParams(max_tokens=21))],
    )
    assert len(outs["a"]) == 21


def test_sampled_path_runs_and_respects_budget():
    outs, finish = drain(
        make_engine(4),
        [("a", "stochastic decode", SamplingParams(
            max_tokens=11, temperature=0.9, top_p=0.9, seed=7))],
    )
    assert len(outs["a"]) == 11
    assert finish["a"] == FinishReason.LENGTH


def test_penalties_run_on_device_with_parity():
    engine = make_engine(4)
    assert engine._window_fn is not None
    reqs = [
        ("pen", "repeat repeat repeat", SamplingParams(
            max_tokens=9, presence_penalty=0.5)),
        ("plain", "other request", SamplingParams(max_tokens=9)),
    ]
    outs, _ = drain(engine, reqs)
    # Penalty batches now run INSIDE the window scan (device-resident
    # occurrence counts) — no fallback, and greedy streams match the
    # single-step host path exactly.
    assert engine.multistep_fallback == {}
    ref, _ = drain(make_engine(1), reqs)
    assert outs == ref
    assert len(outs["pen"]) == 9
    assert len(outs["plain"]) == 9


def test_multi_step_matches_under_continuous_batching():
    """Requests arriving mid-flight (prefill interleaved with multi-step
    decode) still produce greedy-parity outputs."""
    def run(n_steps):
        engine = make_engine(n_steps)
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

    assert run(1) == run(4)


def test_prefix_cache_not_polluted_by_overrun():
    """Discarded overrun tokens write KV past the kept sequence; those
    slots must never enter the prefix cache (full-block registration
    boundary).  A follow-up request with the same prompt must still get
    greedy-parity output."""
    engine = make_engine(8)
    sp = SamplingParams(max_tokens=5)
    first, _ = drain(engine, [("a", "shared prefix prompt", sp)])
    second, _ = drain(engine, [("b", "shared prefix prompt", sp)])
    assert first["a"] == second["b"]
    ref, _ = drain(make_engine(1), [("r", "shared prefix prompt", sp)])
    assert second["b"] == ref["r"]
