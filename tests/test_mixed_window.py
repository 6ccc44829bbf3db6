"""Mixed K-step windows (SchedulerConfig.mixed_window): a waiting
prompt's prefill chunks ride the device-resident decode scan, so
sustained arrivals stop forcing K=1 steps.

The tentpole contract (docs/engine.md, "Unified step plan"): when a
multi-chunk prompt waits, ``schedule()`` emits a StepPlan with a
``chunk_schedule`` — up to decode_window scan iterations, each running
the packed [decode + chunk] mixed forward with the chunk cursor carried
in-graph, a final chunk handing the next iteration to the next waiter —
and the window always ENDS at an admission boundary, which is what
keeps greedy streams byte-identical and seeded streams bit-identical to
the ``--no-mixed-window`` K=1 escape hatch (iteration t of a window
dispatched at counter c IS step c+t of the K=1 world, chunk shapes
included).  ``schedule_provisional_window`` chains mixed windows off
the in-flight carry so the pipeline never drains through an admission.
"""

import pathlib
import re

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
    SamplingParams,
    Sequence,
)
from production_stack_tpu.engine.kv.block_pool import BlockPool


def make_engine(mixed_window=True, seed=0, **sched_kw):
    """mixed_window=False is the --no-mixed-window escape hatch: the
    K=1 mixed scheduling of PR 3/8, byte-for-byte."""
    sched = dict(
        max_num_seqs=2,
        prefill_buckets=(16, 32, 64, 128),
        prefill_chunk_buckets=(16,),
        max_model_len=256,
    )
    if not mixed_window:
        sched["mixed_window"] = False
    sched.update(sched_kw)
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=160),
        scheduler=SchedulerConfig(**sched),
        seed=seed,
    ))


RUN_PROMPT = [(7 * i) % 101 for i in range(24)]
LONG_PROMPT = [(3 * i + 1) % 97 for i in range(80)]  # 5 chunks of 16


def run_midstream(eng, sp_kwargs=None, arrive_after=5, late_prompt=None):
    """One running stream; a (long) prompt arrives after the stream has
    emitted ``arrive_after`` tokens — the sustained-arrival shape."""
    sp_kwargs = sp_kwargs or {}
    eng.add_request(
        "a", prompt_token_ids=list(RUN_PROMPT),
        sampling_params=SamplingParams(
            max_tokens=40, ignore_eos=True, **sp_kwargs),
    )
    outs = {}
    fired = False
    steps = 0
    while eng.has_unfinished():
        steps += 1
        assert steps < 800, "engine failed to drain"
        for out in eng.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
        if not fired and len(outs.get("a", [])) >= arrive_after:
            eng.add_request(
                "b",
                prompt_token_ids=list(late_prompt or LONG_PROMPT),
                sampling_params=SamplingParams(
                    max_tokens=20, ignore_eos=True, **sp_kwargs),
            )
            fired = True
    return outs


# -- config resolution ------------------------------------------------------


def test_mixed_window_default_on_and_gate_off():
    cfg = SchedulerConfig()
    assert cfg.mixed_window_enabled
    assert not SchedulerConfig(mixed_window=False).mixed_window_enabled
    # Requires both parents: no window machinery -> no mixed windows.
    assert not SchedulerConfig(
        multi_step_window=False).mixed_window_enabled
    assert not SchedulerConfig(mixed_batch=False).mixed_window_enabled
    # Directly contradictory explicit combos refuse loudly.
    with pytest.raises(ValueError, match="mixed_window"):
        SchedulerConfig(mixed_window=True, multi_step_window=False)
    with pytest.raises(ValueError, match="mixed_window"):
        SchedulerConfig(mixed_window=True, mixed_batch=False)


def test_escape_hatches_compose():
    """--no-mixed-window composes with the other escape hatches."""
    cfg = SchedulerConfig(mixed_window=False, multi_step_window=False)
    assert cfg.window_steps == 1 and not cfg.mixed_window_enabled
    cfg = SchedulerConfig(mixed_window=False, mixed_batch=False)
    assert not cfg.mixed_enabled and not cfg.mixed_window_enabled


# -- scheduler plan shapes --------------------------------------------------


def _scheduler(**kw):
    pool = BlockPool(num_blocks=256, block_size=4)
    cfg = SchedulerConfig(
        max_num_seqs=kw.pop("max_num_seqs", 4),
        prefill_buckets=(16, 32, 64),
        prefill_chunk_buckets=kw.pop("prefill_chunk_buckets", (16,)),
        max_model_len=512,
        **kw,
    )
    return Scheduler(cfg, pool), pool


def test_mixed_window_plan_shape_and_boundary():
    sched, _ = _scheduler()
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    assert sched.schedule().prefill_chunk is not None  # classic prefill
    run.output_token_ids.append(1)
    sched.add_seq(
        Sequence("wait", list(LONG_PROMPT), SamplingParams(max_tokens=8))
    )
    plan = sched.schedule()
    assert isinstance(plan, StepPlan)
    # 80 tokens / 16-token chunks = 5 chunks <= K=8: ONE window covers
    # the whole prefill and ends AT the admission boundary (last chunk
    # final) — never past it.
    assert plan.chunk_schedule is not None
    assert plan.decode_window == len(plan.chunk_schedule) == 5
    assert all(not cp.is_final for cp in plan.chunk_schedule[:-1])
    assert plan.chunk_schedule[-1].is_final
    # Every chunk shares ONE static bucket and the cursor advances by
    # exactly the chunk length (the in-graph carry's schedule).
    assert {cp.bucket_len for cp in plan.chunk_schedule} == {16}
    cursors = [cp.cached_len for cp in plan.chunk_schedule]
    assert cursors == [16 * i for i in range(5)]
    # Decode rows got the whole window as budget.
    assert plan.decode is not None and plan.decode.steps == [5]
    assert plan.window_fallback is None


def test_longer_prompt_chunks_across_chained_windows():
    sched, _ = _scheduler(prefill_chunk_buckets=(16,), max_num_seqs=2)
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    sched.schedule()
    run.output_token_ids.append(1)
    long = Sequence(
        "wait", [(5 * i) % 89 for i in range(300)],
        SamplingParams(max_tokens=8),
    )
    sched.add_seq(long)
    plan = sched.schedule()
    # 300 tokens needs 19 chunks > K=8: the window fills its K=8 budget
    # with non-final chunks and the prompt continues next window.
    assert plan.chunk_schedule is not None
    assert len(plan.chunk_schedule) == 8
    assert not plan.chunk_schedule[-1].is_final
    assert long.partial_prefill
    assert long.num_cached_tokens == 8 * 16


def test_single_chunk_head_is_not_a_fallback():
    """A head that fits one chunk bucket admits completely in one K=1
    mixed step — nothing was forfeited, so waiting_head must NOT count
    (the CI smoke asserts the series stays zero on a loaded run)."""
    sched, _ = _scheduler(prefill_chunk_buckets=(16, 32))
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    sched.schedule()
    run.output_token_ids.append(1)
    sched.add_seq(Sequence("short", [1, 2, 3, 4, 5, 6],
                           SamplingParams(max_tokens=8)))
    plan = sched.schedule()
    assert plan.chunk_schedule is None and plan.decode_window == 1
    assert plan.prefill_chunk is not None and plan.prefill_chunk.is_final
    assert plan.window_fallback is None


def test_no_mixed_window_restores_k1_plans():
    sched, _ = _scheduler(mixed_window=False)
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    sched.schedule()
    run.output_token_ids.append(1)
    sched.add_seq(
        Sequence("wait", list(LONG_PROMPT), SamplingParams(max_tokens=8))
    )
    plan = sched.schedule()
    assert plan.chunk_schedule is None and plan.decode_window == 1
    assert plan.prefill_chunk is not None
    assert plan.window_fallback == "waiting_head"


# -- engine parity ----------------------------------------------------------


def test_greedy_parity_and_windows_engage():
    eng = make_engine(True)
    got = run_midstream(eng)
    assert eng._mixed_window_fn is not None
    assert eng.mixed_window_chunk_tokens == len(LONG_PROMPT)
    assert eng.multistep_fallback == {}
    ref_eng = make_engine(False)
    ref = run_midstream(ref_eng)
    assert ref_eng.multistep_fallback.get("waiting_head", 0) > 0
    assert ref_eng.mixed_window_chunk_tokens == 0
    assert got == ref, "greedy divergence mixed-window vs K=1"


def test_seeded_sampling_bit_identical():
    """The window ends at the admission boundary, so the key-ordinal
    stream (PRNGKey(seed + c + t) per iteration, the final chunk's
    first token at its iteration's ordinal) is exactly the K=1 path's."""
    sp = dict(temperature=0.9, top_p=0.9, seed=7)
    ref = run_midstream(make_engine(False), sp)
    got = run_midstream(make_engine(True), sp)
    assert got == ref


def test_penalties_min_tokens_through_mixed_windows():
    sp = dict(repetition_penalty=1.3, presence_penalty=0.5, min_tokens=6)
    ref = run_midstream(make_engine(False), sp)
    eng = make_engine(True)
    got = run_midstream(eng, sp)
    assert eng.multistep_fallback == {}
    assert got == ref


def test_spec_ngram_composes_with_mixed_windows():
    """The {K=8 mixed + ngram=3} grid cell: drafting engages in
    pure-decode windows, mixed windows keep the plain per-iteration
    advance, and greedy streams stay byte-identical to the K=1 path."""
    ref = run_midstream(make_engine(False))
    eng = make_engine(True, speculative_ngram=3)
    got = run_midstream(eng)
    assert got == ref
    assert eng.multistep_fallback == {}
    assert eng.mixed_window_chunk_tokens == len(LONG_PROMPT)


def test_logprobs_decode_row_declines_window():
    """A host-state decode row (logprobs) must keep the batch off the
    window scan — the scheduler reads the SAME host_state_flags the
    engine's dispatch gate does, so it never plans a mixed window the
    engine would fall back out of."""
    eng = make_engine(True)
    eng.add_request(
        "a", prompt_token_ids=list(RUN_PROMPT),
        sampling_params=SamplingParams(
            max_tokens=30, ignore_eos=True, logprobs=True, top_logprobs=2),
    )
    outs = {}
    fired = False
    steps = 0
    while eng.has_unfinished():
        steps += 1
        assert steps < 800
        for out in eng.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
        if not fired and len(outs.get("a", [])) >= 5:
            eng.add_request(
                "b", prompt_token_ids=list(LONG_PROMPT),
                sampling_params=SamplingParams(max_tokens=8))
            fired = True
    assert eng.mixed_window_chunk_tokens == 0
    assert eng.multistep_fallback.get("logprobs", 0) > 0
    assert len(outs["b"]) == 8


def test_cross_instance_lockstep_determinism_with_chunk_in_flight():
    """Two engine instances with identical seeds must produce identical
    sampled streams AND identical window/chunk accounting while a chunk
    schedule rides the scan — the mixed window's carry is a pure
    function of config seed + step counter + carried state, never
    instance identity or wall clock (the multi-host lockstep bar)."""
    sp = dict(temperature=1.0, top_p=0.95, seed=42)
    one = make_engine(True, seed=1234)
    two = make_engine(True, seed=1234)
    outs_one = run_midstream(one, sp)
    outs_two = run_midstream(two, sp)
    assert outs_one == outs_two
    assert one.mixed_window_chunk_tokens == two.mixed_window_chunk_tokens
    assert one.multistep_fallback == two.multistep_fallback
    # A different config seed actually changes the sampled streams.
    other = run_midstream(make_engine(True, seed=99), sp)
    assert other != outs_one


def test_abort_mid_mixed_window_counts_chunk_waste():
    """A prompt aborted while its chunk schedule is in flight: the
    chunk KV already written on-device is unreachable — counted into
    tpu:multistep_wasted_tokens_total, never silently vanished."""
    eng = make_engine(True)
    eng.add_request(
        "a", prompt_token_ids=list(RUN_PROMPT),
        sampling_params=SamplingParams(max_tokens=64, ignore_eos=True),
    )
    # Let the stream settle into decoding, then drain the pipeline so
    # the next dispatch is the mixed window.
    for _ in range(4):
        eng.step()
    while eng.has_pending():
        eng.collect()
    eng.add_request(
        "b", prompt_token_ids=list(LONG_PROMPT),
        sampling_params=SamplingParams(max_tokens=8, ignore_eos=True),
    )
    assert eng.dispatch()
    pending = list(eng._pending)
    assert any(p.chunk_sched is not None for p in pending), (
        "mixed window did not dispatch"
    )
    wasted0 = eng.multistep_wasted_tokens
    eng.abort_request("b")
    while eng.has_pending():
        eng.collect()
    chunk_in_flight = sum(
        sum(cp.num_new_tokens for cp in p.chunk_sched)
        for p in pending if p.chunk_sched is not None
    )
    assert eng.multistep_wasted_tokens - wasted0 >= chunk_in_flight
    assert eng.mixed_window_chunk_tokens == 0
    # The survivor drains cleanly.
    while eng.has_unfinished():
        eng.step()


def test_mixed_windows_chain_through_pipeline():
    """Sustained arrivals keep the pipeline full: a mixed window chains
    off the in-flight carry (provisional path) instead of draining the
    device at the admission."""
    eng = make_engine(True)
    eng.add_request(
        "a", prompt_token_ids=list(RUN_PROMPT),
        sampling_params=SamplingParams(max_tokens=64, ignore_eos=True),
    )
    saw_chained_mixed = False
    outs = {}
    fired = False
    steps = 0
    while eng.has_unfinished():
        steps += 1
        assert steps < 800
        eng.dispatch()
        if (
            len(eng._pending) == 2
            and eng._pending[1].chunk_sched is not None
        ):
            saw_chained_mixed = True
        for out in eng.collect():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
        if not fired and len(outs.get("a", [])) >= 5:
            eng.add_request(
                "b", prompt_token_ids=list(LONG_PROMPT),
                sampling_params=SamplingParams(
                    max_tokens=8, ignore_eos=True))
            fired = True
    assert saw_chained_mixed, (
        "no mixed window chained off an in-flight carry"
    )


def test_ttft_steps_bounded_under_sustained_arrivals():
    """The north-star regime: prompts keep arriving, and each one's
    first token still lands within a bounded number of engine steps of
    its arrival (admission is re-evaluated at every window boundary;
    the window length is capped by the chunk count, so a waiter is
    never stuck behind more than one window)."""
    eng = make_engine(True, max_num_seqs=4)
    eng.add_request(
        "a", prompt_token_ids=list(RUN_PROMPT),
        sampling_params=SamplingParams(max_tokens=60, ignore_eos=True),
    )
    arrivals = {}  # rid -> step index at arrival
    first_tok = {}
    step = 0
    next_idx = 0
    while eng.has_unfinished():
        step += 1
        assert step < 1000
        for out in eng.step():
            if out.seq_id not in first_tok:
                first_tok[out.seq_id] = step
        if next_idx < 3 and step % 6 == 0:
            rid = f"p{next_idx}"
            eng.add_request(
                rid, prompt_token_ids=list(LONG_PROMPT),
                sampling_params=SamplingParams(
                    max_tokens=6, ignore_eos=True))
            arrivals[rid] = step
            next_idx += 1
    for rid, t0 in arrivals.items():
        # One in-flight window + its own chunk window + pipeline slack.
        assert first_tok[rid] - t0 <= 12, (
            f"{rid} waited {first_tok[rid] - t0} steps for TTFT"
        )


def test_all_finished_drop_never_discards_a_chunked_window():
    """collect()'s drop-successors shortcut ("every decode row finished
    -> the queued window is a pure no-op") must NOT apply to a mixed
    window: its chunk head is not a decode row, and dropping it would
    skip the final chunk's first-token finalization for a prompt whose
    KV the device already wrote."""
    import numpy as np

    from production_stack_tpu.engine.core.engine import _PendingStep
    from production_stack_tpu.engine.core.sequence import (
        FinishReason,
        SequenceStatus,
    )

    eng = make_engine(True)
    done = Sequence("done", [1, 2, 3], SamplingParams(max_tokens=4))
    done.status = SequenceStatus.FINISHED
    done.finish_reason = FinishReason.ABORT
    head = Sequence("head", list(range(32)), SamplingParams(max_tokens=4))
    from production_stack_tpu.engine.core.scheduler import PrefillPlan

    chunk = PrefillPlan(
        seq=head, bucket_len=16, new_block_ids=[0] * 4,
        prefix_block_ids=[], num_new_tokens=16, cached_len=0,
        is_final=False,
    )
    prev = _PendingStep(
        seqs=[done], sampled=np.full((2, 1), -1, np.int32), steps=[2],
        is_decode=True,
    )
    succ_plain = _PendingStep(
        seqs=[done], sampled=np.full((2, 1), -1, np.int32), steps=[2],
        is_decode=True,
    )
    succ_mixed = _PendingStep(
        seqs=[done], sampled=np.full((2, 1), -1, np.int32), steps=[2],
        is_decode=True, chunk_sched=[chunk],
    )
    eng._pending.extend([prev, succ_plain, succ_mixed])
    eng.collect()  # pops prev; the drop loop inspects the successors
    assert not any(p is succ_plain for p in eng._pending), (
        "all-finished plain successor should have been dropped"
    )
    assert any(p is succ_mixed for p in eng._pending), (
        "mixed window with a live chunk schedule must survive the drop"
    )
    eng._pending.clear()


def test_k1_fallback_respects_spec_budget_block_invariant():
    """A waiting head served at K=1 on a speculative engine: the drafter
    engages only inside a window, so every decode row's K=1 budget is one
    token — the plain decode step's — and its block table covers it (a
    short table is a step-thread crash)."""
    pool = BlockPool(num_blocks=256, block_size=4)
    cfg = SchedulerConfig(
        max_num_seqs=8, prefill_buckets=(16, 32, 64),
        prefill_chunk_buckets=(16, 32), max_model_len=512,
        decode_window=8, speculative_ngram=3, pipeline_decode=False,
        mixed_window=False,
    )
    sched = Scheduler(cfg, pool)
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    sched.schedule()
    run.output_token_ids.append(1)
    # Head: 40 tokens -> chunk 1 at bucket 32 (non-final): the pass is a
    # K=1 mixed step, counted as the forfeit it is.
    sched.add_seq(Sequence("head", list(range(40)),
                           SamplingParams(max_tokens=8)))
    for i in range(2):
        sched.add_seq(Sequence(f"w{i}", list(range(40)),
                               SamplingParams(max_tokens=8)))
    plan = sched.schedule()
    assert plan.decode_window == 1 and plan.chunk_schedule is None
    assert plan.prefill_chunk is not None
    assert plan.window_fallback == "waiting_head"
    bs = pool.block_size
    for seq, k in zip(plan.decode.seqs, plan.decode.steps):
        assert k == 1
        assert len(seq.block_table) * bs >= seq.num_tokens, (
            f"{seq.seq_id}: budget {k} not block-backed"
        )


# -- packed multi-prompt windows --------------------------------------------


@pytest.mark.parametrize("gate, packs, window", [
    ({}, True, 4),
    ({"mixed_window": False}, False, 4),
    # What the benchmark's cells run (--no-mixed-batch): the exception uses
    # nothing of the mixed machinery and no longer hangs on it (ROADMAP S6).
    ({"mixed_batch": False}, False, 4),
    ({"multi_step_window": False}, False, 1),
])
def test_multi_prompt_window_default_on_and_gate(gate, packs, window):
    """Packing is what a mixed window does: it follows
    mixed_window_enabled.  The packed-window exception does not (PR 60: the
    one window-length rule, Scheduler._plan_window) — a slot-full batch
    runs a pure-decode window past a waiting prompt whatever the gate (no
    admission fits either way), ending with the first step a slot could
    free."""
    assert SchedulerConfig(**gate).mixed_window_enabled == packs
    sched, _ = _scheduler(max_num_seqs=2, **gate)
    for i, budget in enumerate((64, 5)):
        seq = Sequence(f"run{i}", list(RUN_PROMPT),
                       SamplingParams(max_tokens=budget))
        sched.add_seq(seq)
        while sched.num_waiting:  # the second admits as chunks, K=1
            sched.schedule()
        seq.output_token_ids.append(1)
    sched.add_seq(Sequence("wait", list(LONG_PROMPT),
                           SamplingParams(max_tokens=8)))
    plan = sched.schedule()
    assert plan.prefill_chunk is None and plan.chunk_schedule is None
    assert [s.seq_id for s in plan.decode.seqs] == ["run0", "run1"]
    # min(window 8, run1's 4 tokens left): the boundary is where a slot
    # frees and admission becomes possible again.  Without windows: a step.
    assert plan.decode_window == window and plan.window_fallback is None
    assert plan.window_cut == ("finish" if window > 1 else None)
    assert plan.decode.steps == [window, window]


def test_packed_window_plans_multiple_prompts():
    """Three 2-chunk waiters pack back-to-back into ONE window: each
    final chunk admits its prompt mid-schedule and the next iteration
    starts the next waiter's cursor — no clamp by queue depth, no
    waiting_head fallback."""
    sched, _ = _scheduler()
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    sched.schedule()
    run.output_token_ids.append(1)
    for i in range(3):
        sched.add_seq(Sequence(
            f"w{i}", [(3 * j + i) % 97 for j in range(32)],
            SamplingParams(max_tokens=8),
        ))
    plan = sched.schedule()
    assert plan.chunk_schedule is not None
    assert plan.window_fallback is None
    # 3 waiters x 2 chunks of 16 = 6 iterations, then slots are full
    # (max_num_seqs=4: run + 3 admitted) so the window ends at 6 < 8.
    assert len(plan.chunk_schedule) == 6
    by_seq = [cp.seq.seq_id for cp in plan.chunk_schedule]
    assert by_seq == ["w0", "w0", "w1", "w1", "w2", "w2"]
    finals = [cp.is_final for cp in plan.chunk_schedule]
    assert finals == [False, True] * 3
    # All three prompts admitted at plan time; decode budget covers the
    # whole window for the pre-existing row.
    assert {s.seq_id for s in sched.running} == {"run", "w0", "w1", "w2"}
    assert plan.decode.steps == [6]


def test_packed_window_forces_first_chunk_bucket():
    """After the first chunk establishes bucket T, every later chunk in
    the window rides at T — a bucket-mismatched final chunk (the PR-15
    K=1 fallback trigger) PACKS instead: is_final with num_new <= T and
    padded rows masked by valid_len."""
    sched, _ = _scheduler(prefill_chunk_buckets=(16, 32))
    run = Sequence("run", list(RUN_PROMPT), SamplingParams(max_tokens=64))
    sched.add_seq(run)
    sched.schedule()
    run.output_token_ids.append(1)
    # 40 tokens: chunk 1 at bucket 32 (non-final), remaining 8 would
    # naturally pick bucket 16 != 32 — forced to ride at 32.
    sched.add_seq(Sequence("head", list(range(40)),
                           SamplingParams(max_tokens=8)))
    sched.add_seq(Sequence("next", list(range(40)),
                           SamplingParams(max_tokens=8)))
    plan = sched.schedule()
    assert plan.chunk_schedule is not None
    assert plan.window_fallback is None
    assert {cp.bucket_len for cp in plan.chunk_schedule} == {32}
    head_chunks = [cp for cp in plan.chunk_schedule
                   if cp.seq.seq_id == "head"]
    assert [cp.num_new_tokens for cp in head_chunks] == [32, 8]
    assert head_chunks[-1].is_final
    # The next waiter's chunks ride the same window at the same bucket.
    assert any(cp.seq.seq_id == "next" for cp in plan.chunk_schedule)


def test_packed_planning_budget_is_o1_in_queue_depth():
    """The chunk-token budget is computed ONCE per scheduler pass no
    matter how many waiters the packed planner walks (the PR-15 code
    recomputed it per chunk; over 16 waiters that was O(K) redundant
    passes over the running set)."""
    deltas = {}
    for n_wait in (2, 16):
        sched, _ = _scheduler(max_num_seqs=20)
        run = Sequence("run", list(RUN_PROMPT),
                       SamplingParams(max_tokens=64))
        sched.add_seq(run)
        sched.schedule()
        run.output_token_ids.append(1)
        for i in range(n_wait):
            sched.add_seq(Sequence(
                f"w{i}", list(LONG_PROMPT), SamplingParams(max_tokens=8)
            ))
        before = sched.budget_computations
        plan = sched.schedule()
        assert plan.chunk_schedule is not None
        assert len({cp.seq.seq_id for cp in plan.chunk_schedule}) >= 2
        deltas[n_wait] = sched.budget_computations - before
    assert deltas[16] == deltas[2] == 1, deltas


def test_packed_greedy_parity_grid():
    """Packed greedy parity over {P=1, P=4} x {K=1, K=8}: byte-identical
    streams whether prompts arrive one at a time or four at once, with
    windows on or the K=1 escape hatch — greedy sampling is a pure
    per-row function of context, packing only changes the schedule."""
    prompts = {
        f"p{i}": [(3 * j + 7 * i + 1) % 97 for j in range(32)]
        for i in range(4)
    }

    def run_grid(mixed_window, burst):
        eng = make_engine(mixed_window, max_num_seqs=6)
        eng.add_request(
            "a", prompt_token_ids=list(RUN_PROMPT),
            sampling_params=SamplingParams(max_tokens=40, ignore_eos=True),
        )
        outs = {}
        sent = 0
        steps = 0
        while eng.has_unfinished():
            steps += 1
            assert steps < 2000
            for out in eng.step():
                outs.setdefault(out.seq_id, []).append(out.new_token_id)
            if sent < 4 and len(outs.get("a", [])) >= 5:
                n = 4 if burst else 1
                for _ in range(n):
                    if sent < 4:
                        rid = f"p{sent}"
                        eng.add_request(
                            rid, prompt_token_ids=list(prompts[rid]),
                            sampling_params=SamplingParams(
                                max_tokens=8, ignore_eos=True))
                        sent += 1
        return outs

    ref = run_grid(mixed_window=False, burst=False)
    for mixed_window in (True, False):
        for burst in (True, False):
            got = run_grid(mixed_window, burst)
            assert got == ref, (
                f"greedy divergence mixed_window={mixed_window} "
                f"burst={burst}"
            )


def test_abort_one_packed_prompt_mid_window():
    """Aborting ONE of the prompts packed into an in-flight window:
    its chunk tokens are counted as waste and its finalize is skipped,
    while the other packed prompt's stream is untouched (same tokens a
    run without the aborted prompt produces)."""
    def script(include_b):
        eng = make_engine(True, max_num_seqs=6)
        # Budget outlasts the warm windows below: "a" must still be
        # decoding when b/c arrive, or no mixed window can form.
        eng.add_request(
            "a", prompt_token_ids=list(RUN_PROMPT),
            sampling_params=SamplingParams(max_tokens=64, ignore_eos=True),
        )
        for _ in range(4):
            eng.step()
        while eng.has_pending():
            eng.collect()
        if include_b:
            eng.add_request(
                "b", prompt_token_ids=[(5 * j + 2) % 89 for j in range(32)],
                sampling_params=SamplingParams(
                    max_tokens=8, ignore_eos=True))
        eng.add_request(
            "c", prompt_token_ids=[(7 * j + 3) % 89 for j in range(32)],
            sampling_params=SamplingParams(max_tokens=8, ignore_eos=True))
        return eng

    eng = script(include_b=True)
    assert eng.dispatch()
    packed = [p for p in eng._pending if p.chunk_sched is not None]
    assert packed, "packed window did not dispatch"
    in_window = {cp.seq.seq_id for p in packed for cp in p.chunk_sched}
    assert {"b", "c"} <= in_window, in_window
    b_tokens = sum(
        cp.num_new_tokens
        for p in packed for cp in p.chunk_sched
        if cp.seq.seq_id == "b"
    )
    wasted0 = eng.multistep_wasted_tokens
    eng.abort_request("b")
    outs = {}
    while eng.has_unfinished():
        for out in eng.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)
    assert "b" not in outs
    assert eng.multistep_wasted_tokens - wasted0 >= b_tokens
    assert len(outs["c"]) == 8

    ref_eng = script(include_b=False)
    ref = {}
    while ref_eng.has_unfinished():
        for out in ref_eng.step():
            ref.setdefault(out.seq_id, []).append(out.new_token_id)
    assert outs["c"] == ref["c"], "abort of b perturbed packed peer c"


def test_overlap_staging_counts_and_preserves_parity():
    """Chained-window H2D staging runs while the device is busy (the
    overlap counter ticks) and the double-buffered staging never
    corrupts an in-flight window's payload — greedy streams stay
    byte-identical to the unpipelined K=1 path."""
    eng = make_engine(True)
    got = run_midstream(eng)
    assert eng.window_transfer_overlap_s > 0, (
        "no H2D staging overlapped an in-flight window"
    )
    ref = run_midstream(make_engine(False))
    assert got == ref


def test_offload_gather_under_inflight_window_counts_overlap():
    """The D2H half of overlap dispatch: an async offload gather
    dispatched while a window is in flight rides the alternate stream
    (counted as avoided stall) and never observes a half-written window
    carry — the in-flight window's collected stream is unchanged."""
    def build():
        eng = LLMEngine(EngineConfig(
            model=ModelConfig(dtype="float32"),
            cache=CacheConfig(
                block_size=4, num_blocks=160, host_offload_gb=0.05),
            scheduler=SchedulerConfig(
                max_num_seqs=2,
                prefill_buckets=(16, 32, 64, 128),
                prefill_chunk_buckets=(16,),
                max_model_len=256,
            ),
        ))
        # Budget outlasts the warm windows: "a" must still be decoding
        # when "b" arrives, or no mixed window can form.
        eng.add_request(
            "a", prompt_token_ids=list(RUN_PROMPT),
            sampling_params=SamplingParams(max_tokens=64, ignore_eos=True),
        )
        for _ in range(4):
            eng.step()
        while eng.has_pending():
            eng.collect()
        eng.add_request(
            "b", prompt_token_ids=list(LONG_PROMPT),
            sampling_params=SamplingParams(max_tokens=8, ignore_eos=True))
        assert eng.dispatch()
        assert any(p.chunk_sched is not None for p in eng._pending)
        return eng

    eng = build()
    seq_a = next(s for s in eng.scheduler.running if s.seq_id == "a")
    before = eng.window_transfer_overlap_s
    assert eng.offload_seq_blocks(seq_a, list(seq_a.block_table)[:2])
    assert eng.window_transfer_overlap_s > before, (
        "in-flight D2H gather not counted as overlap"
    )
    outs = {}
    while eng.has_unfinished():
        for out in eng.step():
            outs.setdefault(out.seq_id, []).append(out.new_token_id)

    ref_eng = build()
    ref = {}
    while ref_eng.has_unfinished():
        for out in ref_eng.step():
            ref.setdefault(out.seq_id, []).append(out.new_token_id)
    assert outs == ref, "mid-flight offload gather perturbed the window"


# -- compat-shim retirement -------------------------------------------------


def test_mixedplan_compat_shim_is_gone():
    """The PR-8 compatibility views are retired: no MixedPlan class, no
    `.mixed` / bare `.prefill` plan views anywhere in the package —
    every caller reads StepPlan fields directly."""
    root = pathlib.Path(__file__).resolve().parents[1]
    pkg = root / "production_stack_tpu"
    offenders = []
    for path in pkg.rglob("*.py"):
        text = path.read_text()
        if re.search(r"\bMixedPlan\b", text):
            offenders.append(f"{path}: MixedPlan")
        # The retired StepPlan views (plan.mixed / plan.prefill); real
        # attribute accesses like `.prefill_chunk`, `self.prefill`, or
        # module functions (llama.prefill) are fine — match the plan
        # variable idiom specifically.
        for m in re.finditer(r"\bplan\.(mixed|prefill)\b(?!_)", text):
            offenders.append(f"{path}: {m.group(0)}")
    assert not offenders, offenders
    import production_stack_tpu.engine.core.scheduler as sched_mod
    assert not hasattr(sched_mod, "MixedPlan")
    assert not hasattr(StepPlan, "mixed")
    assert not hasattr(StepPlan, "prefill")
