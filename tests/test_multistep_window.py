"""K-step device-resident decode windows (SchedulerConfig
multi_step_window / decode_window) — the window-first surface.

The tentpole contract (docs/engine.md, "Unified step plan"): pure-decode
passes run K decode+sample iterations as ONE device dispatch with
penalties and the min_tokens EOS floor applied INSIDE the scan from
device-resident occurrence state, per-row stop masking freezing finished
rows (no trailing tokens, no KV writes past the stop), and window N+1
chained off window N's in-flight carry through the lookahead pipeline.
Greedy output must be byte-identical and seeded-sampling output
bit-identical to single-token stepping (``multi_step_window=False``),
including penalty / min_tokens batches that used to force a fallback.
"""

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.scheduler import Scheduler, StepPlan
from production_stack_tpu.engine.core.sequence import (
    FinishReason,
    SamplingParams,
)


def make_engine(window, seed=0, **sched_kw):
    """window=1 -> single-token reference (multi_step_window=False);
    window>1 -> K-step windows via the window-first decode_window knob."""
    sched = dict(
        max_num_seqs=2,
        prefill_buckets=(16, 32, 64),
        max_model_len=256,
    )
    if window == 1:
        sched["multi_step_window"] = False
    else:
        sched["decode_window"] = window
    sched.update(sched_kw)
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(**sched),
        seed=seed,
    ))


def drain(engine, requests):
    """requests: [(id, prompt-or-token-ids, SamplingParams)];
    returns ({id: tokens}, {id: finish_reason})."""
    for rid, prompt, sp in requests:
        if isinstance(prompt, list):
            engine.add_request(rid, prompt_token_ids=prompt,
                               sampling_params=sp)
        else:
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


# -- config resolution ------------------------------------------------------


def test_window_default_on_and_gate_off():
    assert SchedulerConfig().window_steps == 8
    assert SchedulerConfig(decode_window=4).window_steps == 4
    assert SchedulerConfig(multi_step_window=False).window_steps == 1
    with pytest.raises(ValueError):
        SchedulerConfig(decode_window=0)


def test_speculation_composes_with_window():
    """The PR-11 fusion: speculative_ngram no longer resolves the window
    off — the drafter runs INSIDE the scan, and the per-window token
    ceiling budgets max acceptance (K x (ngram + 1))."""
    cfg = SchedulerConfig(speculative_ngram=3)
    assert cfg.window_steps == 8
    assert cfg.spec_window_enabled
    assert cfg.window_max_tokens == 8 * 4
    assert cfg.pipeline_enabled and cfg.mixed_enabled
    # Explicit window + speculation is a valid (formerly rejected) combo.
    cfg = SchedulerConfig(multi_step_window=True, speculative_ngram=3)
    assert cfg.spec_window_enabled
    cfg = SchedulerConfig(decode_window=4, speculative_ngram=4)
    assert cfg.window_steps == 4 and cfg.spec_window_enabled
    assert cfg.window_max_tokens == 4 * 5


DRAFTERS = {
    "ngram": {"speculative_ngram": 3},
    "model": {"speculative_model": "tiny-llama"},
}


@pytest.mark.parametrize("drafter", sorted(DRAFTERS))
def test_speculation_without_the_window_is_refused(drafter):
    """One rule for both drafters: speculation runs inside the K-step
    window, so a drafter with --no-multi-step-window refuses to boot
    (there is no host-side verify to fall back to), whatever the other
    gates say."""
    for extra in ({}, {"pipeline_decode": False}, {"mixed_batch": False}):
        with pytest.raises(ValueError, match="inside the K-step window"):
            SchedulerConfig(multi_step_window=False, **DRAFTERS[drafter],
                            **extra)
    # The gates no longer look at speculation to resolve "auto".
    cfg = SchedulerConfig(**DRAFTERS[drafter])
    assert cfg.spec_window_enabled and cfg.spec_drafter == drafter
    assert cfg.pipeline_enabled and cfg.mixed_enabled
    assert SchedulerConfig(multi_step_window=False).pipeline_enabled
    assert SchedulerConfig(multi_step_window=False).mixed_enabled


def test_config_composition():
    """Speculation composes with the window by fusing into the scan; a
    drafter left with a one-step window is inert, not refused."""
    cfg = SchedulerConfig(decode_window=4, speculative_ngram=4)
    assert cfg.window_steps == 4 and cfg.spec_window_enabled
    inert = SchedulerConfig(decode_window=1, speculative_ngram=4)
    assert inert.window_steps == 1 and not inert.spec_window_enabled
    assert inert.window_max_tokens == 1
    with pytest.raises(ValueError, match="drop --no-multi-step-window"):
        SchedulerConfig(speculative_ngram=4, multi_step_window=False)


def test_gate_off_restores_single_step_machinery():
    eng = make_engine(1)
    assert eng._window_fn is None
    ref, _ = drain(eng, [("a", "plain request", SamplingParams(max_tokens=9))])
    assert len(ref["a"]) == 9


def test_window_coexists_with_pipeline_and_mixed():
    """The PR-1/PR-3 mutual exclusions are lifted: windows, the lookahead
    pipeline, and mixed batching all resolve ON together by default."""
    cfg = SchedulerConfig()
    assert cfg.window_steps > 1
    assert cfg.pipeline_enabled
    assert cfg.mixed_enabled


# -- parity -----------------------------------------------------------------


def test_greedy_parity_across_window_sizes():
    reqs = [
        ("a", "the quick brown fox", SamplingParams(max_tokens=33)),
        ("b", "pack my box with", SamplingParams(max_tokens=21)),
    ]
    ref, ref_fin = drain(make_engine(1), reqs)
    for k in (4, 8):
        got, got_fin = drain(make_engine(k), reqs)
        assert got == ref, f"greedy divergence at K={k}"
        assert got_fin == ref_fin


def test_seeded_sampling_parity_vs_single_step():
    """The window's PRNGKey(seed + counter + t) schedule burns exactly
    the key ordinals single-token stepping would: seeded sampled streams
    are bit-identical across window sizes."""
    reqs = [
        ("a", "stochastic stream one", SamplingParams(
            max_tokens=17, temperature=0.9, top_p=0.9, seed=7)),
        ("b", "stochastic stream two", SamplingParams(
            max_tokens=17, temperature=0.8, top_k=40, seed=11)),
    ]
    ref, _ = drain(make_engine(1), reqs)
    got, _ = drain(make_engine(8), reqs)
    assert got == ref


def test_penalty_batch_served_on_device_with_parity():
    """Repetition/presence/frequency penalties run INSIDE the scan from
    device-resident occurrence state — no fallback, bit-identical to the
    host single-step path (shared apply_penalties_state kernel)."""
    reqs = [
        ("rep", "repeat repeat repeat repeat", SamplingParams(
            max_tokens=19, repetition_penalty=1.3)),
        ("pf", "penalize me twice", SamplingParams(
            max_tokens=19, presence_penalty=0.7, frequency_penalty=0.4)),
    ]
    eng = make_engine(8)
    got, _ = drain(eng, reqs)
    assert eng.multistep_fallback == {}
    ref, _ = drain(make_engine(1), reqs)
    assert got == ref


def test_seeded_penalty_batch_parity():
    """The combination that used to be impossible on the fused path:
    sampled + penalties + min_tokens, all on-device, bit-identical."""
    reqs = [
        ("x", "sampled and penalized", SamplingParams(
            max_tokens=15, temperature=0.9, seed=3,
            repetition_penalty=1.2, presence_penalty=0.5, min_tokens=6)),
    ]
    ref, _ = drain(make_engine(1), reqs)
    eng = make_engine(8)
    got, _ = drain(eng, reqs)
    assert eng.multistep_fallback == {}
    assert got == ref


def test_lockstep_determinism_across_instances():
    """Two engine INSTANCES with identical seeds produce bit-identical
    sampled multi-step output — the cross-instance parity the multi-host
    lockstep replicas rely on (the per-iteration PRNGKey(seed + c + t)
    schedule must depend only on config seed and step counter, never on
    instance identity or wall clock)."""
    reqs = [
        ("a", "replica determinism check", SamplingParams(
            max_tokens=23, temperature=1.0, top_p=0.95, seed=42)),
        ("b", "second seeded stream", SamplingParams(
            max_tokens=23, temperature=0.7, seed=1)),
    ]
    one, fin_one = drain(make_engine(8, seed=1234), reqs)
    two, fin_two = drain(make_engine(8, seed=1234), reqs)
    assert one == two
    assert fin_one == fin_two
    # A different config seed must actually change the sampled streams
    # (otherwise the test above would pass vacuously on constant output).
    other, _ = drain(make_engine(8, seed=99), reqs)
    assert other != one


# -- device stop-mask -------------------------------------------------------


def _probe_stop_token(prompt, at_least=10):
    """Greedy-reference token first emitted at position >= at_least (and
    not earlier), so a stop_token_ids stop lands mid-stream at a known,
    window-unaligned position."""
    ref, _ = drain(make_engine(1), [
        ("probe", prompt, SamplingParams(max_tokens=40, ignore_eos=True)),
    ])
    toks = ref["probe"]
    for pos in range(at_least, len(toks)):
        if toks[pos] not in toks[:pos]:
            return toks[pos], toks[:pos]
    raise AssertionError("no unique late token in greedy reference")


def test_stop_mid_window_emits_no_trailing_tokens():
    prompt = "stop masking check"
    stop_tok, prefix = _probe_stop_token(prompt)
    # Window size 8 with the stop landing at len(prefix) (not a multiple
    # of 8 by probe construction >= 10, < 16 would be ok too): the row
    # freezes inside the scan.
    eng = make_engine(8)
    got, fin = drain(eng, [
        ("a", prompt, SamplingParams(
            max_tokens=40, ignore_eos=True, stop_token_ids=[stop_tok])),
    ])
    # vLLM stop semantics: the matched token ends generation but is
    # never appended/streamed — the finish event carries the text-free
    # -1 sentinel — and NOTHING follows it: the device mask froze the
    # row, so there are no computed-then-discarded trailing tokens.
    assert got["a"] == prefix + [-1]
    assert fin["a"] == FinishReason.STOP
    assert eng.multistep_wasted_tokens == 0


def test_stop_mask_parity_with_single_step():
    prompt = "stop parity check"
    stop_tok, _ = _probe_stop_token(prompt)
    reqs = [
        ("a", prompt, SamplingParams(
            max_tokens=40, ignore_eos=True, stop_token_ids=[stop_tok])),
        ("b", "unstopped co-batch stream", SamplingParams(max_tokens=29)),
    ]
    ref, ref_fin = drain(make_engine(1), reqs)
    got, got_fin = drain(make_engine(8), reqs)
    assert got == ref
    assert got_fin == ref_fin


def test_stop_does_not_pollute_prefix_cache():
    """Frozen rows park KV writes on null block 0: no cache slot past
    the stop position is ever written, so a follow-up request sharing
    the prompt gets greedy parity (the observable for 'KV write count
    stops at the stop position' — polluted slots past the stop would
    corrupt the reused prefix)."""
    prompt = "shared prefix stopping early"
    stop_tok, _ = _probe_stop_token(prompt)
    eng = make_engine(8)
    sp_stop = SamplingParams(
        max_tokens=40, ignore_eos=True, stop_token_ids=[stop_tok])
    drain(eng, [("a", prompt, sp_stop)])
    sp_full = SamplingParams(max_tokens=24, ignore_eos=True)
    reused, _ = drain(eng, [("b", prompt, sp_full)])
    fresh, _ = drain(make_engine(8), [("c", prompt, sp_full)])
    ref, _ = drain(make_engine(1), [("r", prompt, sp_full)])
    assert reused["b"] == fresh["c"] == ref["r"]


def test_min_tokens_floor_suppresses_stop_on_device():
    """The min_tokens ban mask (-1e9 on the stop set while the floor is
    unmet) runs inside the scan: a stop token that would fire early is
    suppressed until min_tokens, with single-step parity."""
    prompt = "min tokens floor check"
    stop_tok, prefix = _probe_stop_token(prompt)
    floor = len(prefix) + 6
    reqs = [("a", prompt, SamplingParams(
        max_tokens=40, ignore_eos=True, stop_token_ids=[stop_tok],
        min_tokens=floor))]
    ref, _ = drain(make_engine(1), reqs)
    eng = make_engine(8)
    got, _ = drain(eng, reqs)
    assert eng.multistep_fallback == {}
    assert got == ref
    assert len(got["a"]) >= floor


# -- fallback + waste observability ----------------------------------------


def test_logprobs_request_falls_back_and_counts():
    eng = make_engine(4)
    reqs = [
        ("lp", "logprobs request", SamplingParams(max_tokens=7, logprobs=2)),
        ("plain", "co-scheduled stream", SamplingParams(max_tokens=7)),
    ]
    got, _ = drain(eng, reqs)
    # The whole batch dropped to single-step, visibly.
    assert eng.multistep_fallback.get("logprobs", 0) > 0
    assert eng.stats()["multistep_fallback"]["logprobs"] > 0
    ref, _ = drain(make_engine(1), reqs)
    assert got == ref


@pytest.mark.parametrize("drafter", sorted(DRAFTERS))
def test_speculative_engine_takes_plain_steps_for_a_logprobs_row(drafter):
    """A host-state row drops a speculative engine's batch to K=1 like
    any other window batch, and a K=1 step is the plain decode step:
    nothing drafts, nothing verifies, and the streams are the
    non-speculative engine's."""
    reqs = [
        ("lp", "logprobs request one two one two", SamplingParams(
            max_tokens=12, logprobs=2)),
        # Ends first: no dispatch is left without the logprobs row.
        ("plain", "co-scheduled stream one two one two", SamplingParams(
            max_tokens=6)),
    ]
    eng = make_engine(4, **DRAFTERS[drafter])
    assert eng._spec_window_fn is not None
    got, fin = drain(eng, reqs)
    assert eng.multistep_fallback.get("logprobs", 0) > 0
    assert eng.spec_tokens_drafted == 0
    decodes = [w for w in eng.obs.windows_payload()["windows"]
               if w["kind"] not in ("prefill", "mixed")]
    assert decodes and all(w["k"] == 1 for w in decodes)
    for w in decodes:
        assert "decode_fn" in w["programs"]
        assert not {"spec_window_fn", "argmax_fn"} & set(w["programs"])
    ref, ref_fin = drain(make_engine(1), reqs)
    assert got == ref and fin == ref_fin


def test_abort_mid_window_counts_wasted_tokens():
    """Tokens emitted on-device for a sequence aborted while its window
    was in flight are undeliverable — counted, not silently vanished."""
    eng = make_engine(8)
    eng.add_request("a", prompt="abort me mid window",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    eng.add_request("b", prompt="keep me running",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    for _ in range(3):  # prefills + first windows dispatched
        eng.step()
    eng.abort_request("a")
    while eng.has_unfinished() or eng.has_pending():
        eng.step()
        if not eng.has_unfinished():
            break
    # Drain any still-pending windows so their waste is accounted.
    while eng.has_pending():
        eng.collect()
    assert eng.multistep_wasted_tokens > 0
    assert eng.stats()["multistep_wasted_tokens"] == (
        eng.multistep_wasted_tokens
    )


# -- unified step plan ------------------------------------------------------


def test_step_plan_window_selection_rule():
    """K > 1 pure-decode windows only when no prompt is waiting; a
    waiting head drops the pass to K=1 so admission re-evaluates every
    token (docs/engine.md window-selection rule)."""
    eng = make_engine(8)
    eng.add_request("a", prompt="resident decoder",
                    sampling_params=SamplingParams(
                        max_tokens=48, ignore_eos=True))
    for _ in range(2):
        eng.step()
    sched: Scheduler = eng.scheduler
    plan = sched.schedule()
    assert isinstance(plan, StepPlan)
    assert plan.decode is not None and plan.decode_window == 8
    assert plan.prefill_chunk is None and plan.chunk_schedule is None
    # A waiting prompt forces K=1 (here: the mixed/classic admission
    # path runs, never an 8-step window).
    eng.add_request("b", prompt="newly arrived prompt",
                    sampling_params=SamplingParams(max_tokens=4))
    plan2 = sched.schedule()
    assert plan2.decode_window == 1


def test_windows_chain_through_pipeline():
    """Steady-state pure-decode serving dispatches window N+1 off window
    N's in-flight carry: the pipeline holds two pending windows and the
    host gap collapses (the provisional-window path, not a rebuild)."""
    eng = make_engine(8)
    eng.add_request("a", prompt="chained windows",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    saw_depth_2 = False
    steps = 0
    while eng.has_unfinished():
        steps += 1
        assert steps < 500
        eng.dispatch()
        if (
            len(eng._pending) == 2
            and all(p.win_state is not None for p in eng._pending)
        ):
            saw_depth_2 = True
        eng.collect()
    assert saw_depth_2, "no chained (provisional) window was dispatched"


def test_chained_windows_greedy_parity_across_block_boundaries():
    """Chained windows transfer only new block-table columns; a long
    stream crossing many block_size=4 boundaries must stay greedy-exact."""
    reqs = [("a", "long crossing stream", SamplingParams(
        max_tokens=90, ignore_eos=True))]
    ref, _ = drain(make_engine(1), reqs)
    got, _ = drain(make_engine(8), reqs)
    assert got == ref


# -- fused speculative windows (spec-in-window) -----------------------------


def test_spec_window_greedy_parity():
    """The PR-11 acceptance bar: greedy decode byte-identical across
    {single-step, K=8 window, K=8 window + ngram=3} — the in-scan
    verifier compares the model's own argmax, so acceptance can never
    change the stream, only its cost."""
    reqs = [
        ("a", "the cat sat on the mat the cat sat on", SamplingParams(
            max_tokens=33)),
        ("b", "abc abc abc abc", SamplingParams(max_tokens=21)),
    ]
    ref, ref_fin = drain(make_engine(1), reqs)
    win, win_fin = drain(make_engine(8), reqs)
    eng = make_engine(8, speculative_ngram=3)
    assert eng._spec_window_fn is not None
    fused, fused_fin = drain(eng, reqs)
    assert win == ref and win_fin == ref_fin
    assert fused == ref and fused_fin == ref_fin
    assert eng.multistep_fallback == {}


def test_spec_window_acceptance_counters_consistent():
    """Repetitive prompts draft on-device; accepted + rejected must
    equal drafted, acceptance feeds the tpu:spec_tokens_* family, and
    stats() mirrors the outcome split."""
    eng = make_engine(8, speculative_ngram=3)
    drain(eng, [("a", "one two three one two three one two three",
                 SamplingParams(max_tokens=48, ignore_eos=True))])
    sw = eng.spec_window_tokens
    assert eng.spec_tokens_drafted > 0
    assert 0 <= eng.spec_tokens_accepted <= eng.spec_tokens_drafted
    assert sw["accepted"] == eng.spec_tokens_accepted
    assert sw["accepted"] + sw["rejected"] == eng.spec_tokens_drafted
    assert eng.stats()["spec_window_tokens"] == sw


def test_spec_window_seeded_sampling_bit_identical():
    """Sampled batches never draft (acceptance needs argmax): they run
    the PLAIN window with the classic per-iteration key schedule, so
    seeded streams stay bit-identical across window sizes with
    speculation configured on."""
    reqs = [
        ("a", "stochastic stream one", SamplingParams(
            max_tokens=17, temperature=0.9, top_p=0.9, seed=7)),
        ("b", "stochastic stream two", SamplingParams(
            max_tokens=17, temperature=0.8, top_k=40, seed=11)),
    ]
    ref, _ = drain(make_engine(1), reqs)
    eng = make_engine(8, speculative_ngram=3)
    got, _ = drain(eng, reqs)
    assert got == ref
    assert eng.spec_tokens_drafted == 0  # the drafter never engaged


def test_spec_window_penalties_and_min_tokens_parity():
    """Penalties and the min_tokens floor apply to EVERY accepted token
    sequentially through the shared apply_penalties_state call site —
    greedy parity with the single-step host path, no fallback."""
    reqs = [
        ("rep", "repeat repeat repeat repeat", SamplingParams(
            max_tokens=19, repetition_penalty=1.3)),
        ("pf", "penalize me twice", SamplingParams(
            max_tokens=19, presence_penalty=0.7, frequency_penalty=0.4,
            min_tokens=6)),
    ]
    ref, _ = drain(make_engine(1), reqs)
    eng = make_engine(8, speculative_ngram=3)
    got, _ = drain(eng, reqs)
    assert eng.multistep_fallback == {}
    assert got == ref


def test_spec_window_lockstep_determinism():
    """Two engine instances with identical seeds must produce identical
    streams AND identical acceptance counters — the fused drafter is a
    pure function of the shared weights and carried state (never wall
    clock or instance identity), which is what lets lockstep replicas
    speculate without desyncing."""
    reqs = [
        ("a", "replica determinism check one two one two", SamplingParams(
            max_tokens=29, ignore_eos=True)),
        ("b", "second stream second stream second", SamplingParams(
            max_tokens=29, ignore_eos=True)),
    ]
    one = make_engine(8, seed=1234, speculative_ngram=3)
    two = make_engine(8, seed=1234, speculative_ngram=3)
    outs_one, fin_one = drain(one, reqs)
    outs_two, fin_two = drain(two, reqs)
    assert outs_one == outs_two and fin_one == fin_two
    assert one.spec_tokens_drafted == two.spec_tokens_drafted
    assert one.spec_tokens_accepted == two.spec_tokens_accepted
    assert one.spec_window_tokens == two.spec_window_tokens


def test_spec_stop_mid_window_zero_waste_and_clean_cache():
    """A stop landing mid-window with accepted draft tokens freezes the
    row inside the scan: no trailing tokens, zero waste, and the prefix
    cache stays clean (a follow-up request sharing the prompt keeps
    greedy parity — rejected-draft KV past the stop never registers)."""
    prompt = "stop masking check"
    stop_tok, prefix = _probe_stop_token(prompt)
    eng = make_engine(8, speculative_ngram=3)
    got, fin = drain(eng, [
        ("a", prompt, SamplingParams(
            max_tokens=40, ignore_eos=True, stop_token_ids=[stop_tok])),
    ])
    assert got["a"] == prefix + [-1]
    assert fin["a"] == FinishReason.STOP
    assert eng.multistep_wasted_tokens == 0
    assert eng.spec_window_tokens["wasted"] == 0
    # Prefix-cache cleanliness: the same engine re-serves the prompt.
    sp_full = SamplingParams(max_tokens=24, ignore_eos=True)
    reused, _ = drain(eng, [("b", prompt, sp_full)])
    ref, _ = drain(make_engine(1), [("r", prompt, sp_full)])
    assert reused["b"] == ref["r"]


def test_spec_abort_mid_window_counts_wasted():
    """Drafted-but-undelivered tokens of a sequence aborted while its
    fused window flew are accounted (multistep waste + the spec-window
    outcome split), never silently vanished."""
    eng = make_engine(8, speculative_ngram=3)
    eng.add_request("a", prompt="abort me mid window one two one two",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    eng.add_request("b", prompt="keep me running along here",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    for _ in range(3):
        eng.step()
    eng.abort_request("a")
    while eng.has_unfinished() or eng.has_pending():
        eng.step()
        if not eng.has_unfinished():
            break
    while eng.has_pending():
        eng.collect()
    assert eng.multistep_wasted_tokens > 0
    assert eng.spec_window_tokens["wasted"] == eng.multistep_wasted_tokens
    assert eng.stats()["spec_window_tokens"]["wasted"] > 0


def test_spec_window_admission_mid_stream_parity():
    """Mixed batching composes with the fused speculative window: a
    request arriving while spec windows chain breaks the chain cleanly
    and keeps greedy parity for both streams."""
    def run(spec):
        eng = make_engine(8, speculative_ngram=spec)
        eng.add_request("a", prompt="first stream first stream",
                        sampling_params=SamplingParams(max_tokens=33))
        outs = {}
        fired = False
        steps = 0
        while eng.has_unfinished():
            steps += 1
            assert steps < 500
            for out in eng.step():
                outs.setdefault(out.seq_id, []).append(out.new_token_id)
            if not fired and len(outs.get("a", [])) >= 5:
                eng.add_request("b", prompt="late arrival stream",
                                sampling_params=SamplingParams(max_tokens=33))
                fired = True
        return outs

    assert run(3) == run(0)


def test_admission_mid_stream_parity():
    """A request arriving while windows are chaining must break the
    chain cleanly (provisional planner declines on a waiting head) and
    keep greedy parity for both streams."""
    def run(window):
        eng = make_engine(window)
        eng.add_request("a", prompt="first stream",
                        sampling_params=SamplingParams(max_tokens=33))
        outs = {}
        fired = False
        steps = 0
        while eng.has_unfinished():
            steps += 1
            assert steps < 500
            for out in eng.step():
                outs.setdefault(out.seq_id, []).append(out.new_token_id)
            if not fired and len(outs.get("a", [])) >= 5:
                eng.add_request("b", prompt="late arrival",
                                sampling_params=SamplingParams(max_tokens=33))
                fired = True
        return outs

    assert run(1) == run(8)


async def test_spec_counters_exported_at_metrics():
    """The drafted/accepted counters surface on the engine's /metrics in
    the tpu: vocabulary (dashboards derive the acceptance rate)."""
    import aiohttp
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    config = config_from_preset(
        "tiny-llama",
        **{"scheduler.max_num_seqs": 2, "scheduler.max_model_len": 256,
           "cache.num_blocks": 128, "scheduler.speculative_ngram": 2},
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    url = f"http://127.0.0.1:{server.port}"
    try:
        async with aiohttp.ClientSession() as session:
            async with session.post(f"{url}/v1/completions", json={
                "model": "tiny-llama",
                "prompt": "one two three one two three one two three",
                "max_tokens": 12,
            }) as resp:
                assert resp.status == 200
            async with session.get(f"{url}/metrics") as resp:
                text = await resp.text()
        assert "tpu:spec_tokens_drafted" in text
        assert "tpu:spec_tokens_accepted" in text
        # The fused-window outcome family renders with its closed
        # outcome x drafter label set from boot (this server runs the
        # fused path: spec + the default K-step window).
        for outcome in ("accepted", "rejected", "wasted"):
            for drafter in ("ngram", "model"):
                assert (
                    'tpu:spec_window_tokens_total{outcome="%s",'
                    'drafter="%s"}' % (outcome, drafter)
                    in text
                )
        assert "tpu:spec_draft_fraction_seconds" in text
        # Drafting is opportunistic (depends on n-gram hits in the random
        # model's output); the contract here is exported, parseable,
        # consistent counters.
        def read(name):
            return [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                    if ln.startswith(name + " ")]
        drafted = read("tpu:spec_tokens_drafted")
        accepted = read("tpu:spec_tokens_accepted")
        assert drafted and accepted
        assert 0 <= accepted[0] <= drafted[0] or drafted[0] == 0
    finally:
        await server.close()
