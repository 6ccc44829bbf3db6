"""Draft-MODEL speculative decoding fused into the device-resident scan
(SchedulerConfig.speculative_model): a second tiny model rides the
K-step window as one of two proposal sources behind the shared in-scan
drafting interface.

The tentpole contract (docs/engine.md, "Fused speculative windows"):
the draft model proposes up to speculative_draft_len tokens per scan
iteration autoregressively from its own small device-resident KV cache
(carried through the scan like the n-gram history buffer; blocks from a
dedicated draft pool, target KV capacity untouched), and the target
verifies draft+1 rows in the SAME wide forward the n-gram drafter uses.
Acceptance, penalties, min_tokens, stop masks and the PRNG ordinal
schedule flow through the existing call sites, so greedy streams stay
byte-identical and seeded streams bit-identical across
{none, ngram, model} at every K — and acceptance is a pure function of
weights + carried state, so lockstep replicas cannot desync.
"""

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams


def make_engine(window=8, seed=0, cache_kw=None, **sched_kw):
    """window=1 -> single-token reference (multi_step_window=False);
    window>1 -> K-step windows.  sched_kw selects the drafter."""
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
    cache = dict(block_size=4, num_blocks=128)
    cache.update(cache_kw or {})
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(**cache),
        scheduler=SchedulerConfig(**sched),
        seed=seed,
    ))


def drain(engine, requests):
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


GREEDY_REQS = [
    ("a", "the cat sat on the mat the cat sat on",
     SamplingParams(max_tokens=33)),
    ("b", "free form text with no template at all",
     SamplingParams(max_tokens=21)),
]


# -- config resolution / validation matrix ----------------------------------


def test_drafter_selection_and_budget():
    """speculative_model selects the model drafter through the same
    spec_window machinery the ngram drafter uses; the per-window token
    ceiling budgets max acceptance (K x (draft_len + 1))."""
    cfg = SchedulerConfig(speculative_model="debug-1l",
                          speculative_draft_len=3)
    assert cfg.spec_drafter == "model"
    assert cfg.spec_draft_len == 3
    assert cfg.spec_window_enabled
    assert cfg.window_max_tokens == 8 * 4
    assert SchedulerConfig(speculative_ngram=3).spec_drafter == "ngram"
    assert SchedulerConfig().spec_drafter is None
    assert SchedulerConfig().window_max_tokens == 8


def test_drafter_mutual_exclusion():
    """One proposal source per engine: configuring both drafters is a
    boot-time error, not a silent priority pick."""
    with pytest.raises(ValueError, match="speculative"):
        SchedulerConfig(speculative_model="debug-1l", speculative_ngram=3)


def test_model_drafter_requires_window_machinery():
    """The model drafter runs INSIDE the scan — --no-multi-step-window
    with it is an error, not a silent degrade."""
    with pytest.raises(ValueError, match="inside the K-step window"):
        SchedulerConfig(speculative_model="debug-1l",
                        multi_step_window=False)
    with pytest.raises(ValueError):
        SchedulerConfig(speculative_model="debug-1l",
                        speculative_draft_len=0)
    with pytest.raises(ValueError):
        SchedulerConfig(speculative_model="debug-1l",
                        speculative_draft_pool_blocks=1)


def test_unknown_preset_and_vocab_mismatch_fail_loudly_at_boot():
    """A draft model the registry does not know, or one whose vocab
    mismatches the target's tokenizer, must refuse to boot — a
    mismatched drafter proposes tokens the target cannot accept and
    would silently zero the acceptance rate."""
    with pytest.raises(ValueError, match="preset"):
        make_engine(8, speculative_model="no-such-model")
    # llama-3.2-1b's 128256-entry vocab mismatches tiny-llama's 384
    # (the check fires before any draft weights materialize).
    with pytest.raises(ValueError, match="vocab"):
        make_engine(8, speculative_model="llama-3.2-1b")


# -- the parity matrix: {none, ngram, model} x {K} x {pure, mixed} ----------


def test_greedy_parity_matrix_pure_decode():
    """Greedy byte-identity across {none, ngram, model} x {K=1, K=8}:
    the in-scan verifier compares the target's own argmax, so neither
    drafter can change the stream, only its cost.  (K=1 resolves
    spec_window_enabled off — both drafters go inert, not wrong.)"""
    ref, ref_fin = drain(make_engine(1), GREEDY_REQS)
    for kw in (
        dict(),
        dict(speculative_ngram=3),
        dict(speculative_model="debug-1l", speculative_draft_len=3),
        dict(decode_window=1, speculative_model="debug-1l"),
        dict(decode_window=1, speculative_ngram=3),
    ):
        eng = make_engine(8, **kw) if "decode_window" not in kw \
            else make_engine(8, **kw)
        got, fin = drain(eng, GREEDY_REQS)
        assert got == ref and fin == ref_fin, f"parity broke for {kw}"
        assert eng.multistep_fallback == {}, kw


def test_seeded_sampling_bit_identical_with_model_drafter():
    """Sampled batches never draft (acceptance needs argmax): they run
    the PLAIN window with the classic per-iteration key schedule, so
    seeded streams stay bit-identical with the model drafter configured
    on — and the drafter never engages."""
    reqs = [
        ("a", "stochastic stream one", SamplingParams(
            max_tokens=17, temperature=0.9, top_p=0.9, seed=7)),
        ("b", "stochastic stream two", SamplingParams(
            max_tokens=17, temperature=0.8, top_k=40, seed=11)),
    ]
    ref, _ = drain(make_engine(1), reqs)
    eng = make_engine(8, speculative_model="debug-1l")
    got, _ = drain(eng, reqs)
    assert got == ref
    assert eng.spec_tokens_drafted == 0


def test_mixed_window_parity_across_drafters():
    """A prompt arriving mid-stream rides mixed windows; drafting is
    pure-decode-window-only for BOTH drafters, so the late arrival
    breaks the spec chain cleanly and greedy parity holds for both
    streams across {none, ngram, model}."""
    def run(**kw):
        eng = make_engine(8, **kw)
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
                                sampling_params=SamplingParams(
                                    max_tokens=33))
                fired = True
        return outs

    ref = run()
    assert run(speculative_ngram=3) == ref
    assert run(speculative_model="debug-1l", speculative_draft_len=3) == ref


def test_penalties_and_min_tokens_parity_with_model_drafter():
    """Penalties and the min_tokens floor apply to every accepted token
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
    eng = make_engine(8, speculative_model="debug-1l",
                      speculative_draft_len=3)
    got, _ = drain(eng, reqs)
    assert eng.multistep_fallback == {}
    assert got == ref


# -- acceptance mechanics ---------------------------------------------------


def test_identical_weights_drafter_accepts_nearly_everything():
    """A drafter sharing the target's exact weights (same preset, same
    seed -> same deterministic init) must agree with the target's argmax
    almost token-for-token: dominant acceptance is the end-to-end proof
    that the draft KV prime, the compact-slot/true-RoPE layout and the
    post-acceptance rewind are all exact.  (Not EXACTLY total: the draft
    fills its cache through the decode kernel while the target prefilled
    through the prefill kernel, and the differing batch shapes can flip
    float32 argmax ties on near-degenerate logits.)"""
    eng = make_engine(8, speculative_model="tiny-llama",
                      speculative_draft_len=3)
    got, _ = drain(eng, GREEDY_REQS)
    ref, _ = drain(make_engine(1), GREEDY_REQS)
    assert got == ref
    sw = eng.spec_window_tokens
    accepted = sw.get("accepted", 0)
    rejected = sw.get("rejected", 0)
    assert accepted > 0
    assert accepted >= 4 * max(rejected, 1)
    assert accepted + rejected == eng.spec_tokens_drafted


def test_acceptance_counters_and_stats_mirror():
    """accepted + rejected must equal drafted; acceptance feeds the same
    tpu:spec_tokens_* family; stats() exports the drafter kind and the
    draft-time share (ngram accrues ZERO draft time)."""
    eng = make_engine(8, speculative_model="debug-1l",
                      speculative_draft_len=3)
    drain(eng, [("a", "one two three one two three one two three",
                 SamplingParams(max_tokens=48, ignore_eos=True))])
    sw = eng.spec_window_tokens
    assert eng.spec_tokens_drafted > 0
    assert sw.get("accepted", 0) + sw.get("rejected", 0) == \
        eng.spec_tokens_drafted
    s = eng.stats()
    assert s["spec_drafter"] == "model"
    assert s["spec_window_tokens"] == sw
    assert s["spec_draft_fraction_seconds"] > 0.0

    ng = make_engine(8, speculative_ngram=3)
    drain(ng, [("a", "one two three one two three one two three",
                SamplingParams(max_tokens=48, ignore_eos=True))])
    assert ng.stats()["spec_drafter"] == "ngram"
    assert ng.stats()["spec_draft_fraction_seconds"] == 0.0


def test_lockstep_two_instances_identical_acceptance():
    """Two engine instances with identical seeds must produce identical
    streams AND identical acceptance counters — draft proposals are a
    pure function of draft weights + carried state (never wall clock or
    instance identity), which is what lets lockstep replicas speculate
    without desyncing.  The identical-weights drafter makes this a
    NON-VACUOUS check (acceptance is actually nonzero)."""
    reqs = [
        ("a", "replica determinism check one two one two", SamplingParams(
            max_tokens=29, ignore_eos=True)),
        ("b", "second stream second stream second", SamplingParams(
            max_tokens=29, ignore_eos=True)),
    ]
    one = make_engine(8, seed=1234, speculative_model="tiny-llama",
                      speculative_draft_len=3)
    two = make_engine(8, seed=1234, speculative_model="tiny-llama",
                      speculative_draft_len=3)
    outs_one, fin_one = drain(one, reqs)
    outs_two, fin_two = drain(two, reqs)
    assert outs_one == outs_two and fin_one == fin_two
    assert one.spec_tokens_accepted == two.spec_tokens_accepted > 0
    assert one.spec_tokens_drafted == two.spec_tokens_drafted
    assert one.spec_window_tokens == two.spec_window_tokens


# -- robustness: pool exhaustion, preemption, abort -------------------------


def test_draft_pool_exhaustion_declines_to_plain_windows():
    """A draft pool too small for the batch never stalls and never
    degrades correctness: the window runs PLAIN (no speculation),
    counted under tpu:multistep_fallback_total{reason=draft_pool}, and
    greedy parity holds."""
    ref, ref_fin = drain(make_engine(1), GREEDY_REQS)
    eng = make_engine(8, speculative_model="debug-1l",
                      speculative_draft_len=3,
                      speculative_draft_pool_blocks=2)
    got, fin = drain(eng, GREEDY_REQS)
    assert got == ref and fin == ref_fin
    assert eng.multistep_fallback.get("draft_pool", 0) > 0
    assert eng.spec_tokens_drafted == 0  # speculation never engaged


def test_preemption_resets_draft_kv_coherently():
    """Preemption/restore under a tiny target pool rebuilds the batch:
    the draft KV must be re-primed from the carried history (never
    reused stale), and the target cache stays clean — greedy parity
    with the single-step path, with preemptions actually firing."""
    reqs = [
        ("r0", "alpha bravo charlie forever and ever", SamplingParams(
            max_tokens=24, ignore_eos=True)),
        ("r1", "delta echo foxtrot forevers and more", SamplingParams(
            max_tokens=24, ignore_eos=True)),
    ]
    ref, _ = drain(make_engine(1, cache_kw=dict(host_offload_gb=0.25)),
                   reqs)
    eng = make_engine(
        8, cache_kw=dict(num_blocks=24, host_offload_gb=0.25),
        speculative_model="tiny-llama", speculative_draft_len=3)
    got, _ = drain(eng, reqs)
    assert eng.scheduler.num_preemptions > 0
    assert got == ref


def test_abort_mid_window_counts_wasted_with_model_drafter():
    """Tokens of a sequence aborted while its fused window flew are
    accounted (multistep waste + the spec-window outcome split) and the
    survivor's stream is unharmed — the draft KV rebuild after the
    batch change cannot pollute the target cache (draft writes only
    ever touch the dedicated draft pool)."""
    eng = make_engine(8, speculative_model="tiny-llama",
                      speculative_draft_len=3)
    eng.add_request("a", prompt="abort me mid window one two one two",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    eng.add_request("b", prompt="keep me running along here",
                    sampling_params=SamplingParams(
                        max_tokens=64, ignore_eos=True))
    for _ in range(3):
        eng.step()
    eng.abort_request("a")
    while eng.has_unfinished():
        eng.step()
    while eng.has_pending():
        eng.collect()
    assert eng.multistep_wasted_tokens > 0
    assert eng.spec_window_tokens["wasted"] == eng.multistep_wasted_tokens
    # Target-cache cleanliness: the same engine re-serves a prompt and
    # matches the fresh single-step reference byte-for-byte.
    sp = SamplingParams(max_tokens=16)
    reused, _ = drain(eng, [("c", "keep me running along here", sp)])
    ref, _ = drain(make_engine(1), [("c", "keep me running along here", sp)])
    assert reused == ref


def test_no_multi_step_window_unset_model_restores_today():
    """--no-speculative-model / an unset speculative_model restores the
    ngram-only world exactly: the config resolves identically to a
    config that never mentioned the model drafter."""
    import dataclasses
    base = SchedulerConfig(speculative_ngram=3)
    off = SchedulerConfig(speculative_ngram=3, speculative_model=None)
    assert dataclasses.asdict(base) == dataclasses.asdict(off)
    legacy = SchedulerConfig(multi_step_window=False)
    assert legacy.spec_drafter is None and legacy.window_max_tokens == 1


# -- observability ----------------------------------------------------------


def test_flight_recorder_stamps_drafter_kind():
    """Spec-window flight records carry the proposal source beside the
    spec width, so /debug/windows can say WHICH drafter a slow window
    rode."""
    from production_stack_tpu.engine.config import config_from_preset

    eng = LLMEngine(config_from_preset(
        "tiny-llama",
        **{"cache.num_blocks": 128, "scheduler.max_num_seqs": 2,
           "scheduler.prefill_buckets": (16, 32),
           "scheduler.speculative_model": "tiny-llama",
           "scheduler.speculative_draft_len": 3},
    ))
    eng.add_request("a", prompt_token_ids=[3, 5, 7, 11],
                    sampling_params=SamplingParams(
                        max_tokens=24, ignore_eos=True))
    while eng.has_unfinished():
        eng.step()
    spec_windows = [d for d in eng.obs.recorder.snapshot()
                    if d["kind"] == "spec"]
    assert spec_windows
    assert all(d["drafter"] == "model" for d in spec_windows)
    assert all(d["spec_width"] == 3 for d in spec_windows)
