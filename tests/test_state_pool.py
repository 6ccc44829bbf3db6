"""The state pool beside the block pool (``engine/kv/state_pool.py``) and the
scheduler's admission over both: host bookkeeping only, no device."""

import pytest

from production_stack_tpu.engine.config import SchedulerConfig
from production_stack_tpu.engine.core.scheduler import Scheduler
from production_stack_tpu.engine.core.sequence import SamplingParams, Sequence
from production_stack_tpu.engine.kv.block_pool import BlockPool
from production_stack_tpu.engine.kv.state_pool import StatePool, pool_slots

BS, STRIDE = 4, 8


def make(num_blocks=64, max_num_seqs=4, live=6, snapshots=4, caching=True,
         run=1):
    blocks = BlockPool(num_blocks, BS, enable_prefix_caching=caching, run=run)
    states = StatePool(live, snapshots)
    cfg = SchedulerConfig(
        max_num_seqs=max_num_seqs, prefill_buckets=(16, 32),
        max_prefill_tokens=32, max_model_len=128, mixed_batch=False)
    return Scheduler(cfg, blocks, state_pool=states,
                     state_stride=STRIDE), blocks, states


def seq(seq_id, tokens, max_tokens=4):
    return Sequence(seq_id=seq_id, prompt_token_ids=list(tokens),
                    sampling_params=SamplingParams(max_tokens=max_tokens))


def prefill(sched, s):
    """Run ``s`` through its prefill chunks; the plans, first to last."""
    sched.add_seq(s)
    plans = []
    while not plans or not plans[-1].is_final:
        plan = sched.schedule().prefill_chunk
        assert plan is not None and plan.seq is s
        plans.append(plan)
    return plans


def finish(sched, s, answer=(901, 902, 903)):
    s.output_token_ids.extend(answer)
    sched.finish_seq(s)


@pytest.mark.parametrize("seqs, want", [(16, (18, 40)), (4, (6, 10)),
                                        (1, (3, 8))])
def test_the_pool_is_sized_by_rule_from_the_batch(seqs, want):
    assert pool_slots(seqs) == want


def test_no_slot_is_handed_twice_and_slot_0_never():
    pool = StatePool(3, 2)
    assert pool.num_slots == 6
    live = [pool.allocate_live(f"s{i}") for i in range(3)]
    snaps = [pool.take_snapshot(bytes([i])) for i in range(2)]
    assert sorted(live + snaps) == [1, 2, 3, 4, 5]
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate_live("one too many")
    pool.free_live(live[1])
    assert pool.allocate_live("again") == live[1]
    with pytest.raises(RuntimeError, match="not held"):
        pool.free_live(snaps[0])
    assert pool.slots_in_use == 5


def test_snapshots_go_by_lru_and_a_resume_touches():
    pool = StatePool(1, 3)
    a, b, c = (pool.take_snapshot(d) for d in (b"a", b"b", b"c"))
    assert pool.resume(b"a") == a            # a is now the newest
    assert pool.take_snapshot(b"d") == b     # b was the oldest
    assert not pool.has_snapshot(b"b") and pool.has_snapshot(b"a")
    assert pool.take_snapshot(b"c") == c     # the one that holds it already
    assert pool.snapshots_taken == 4 and pool.resumes == 1
    pool.drop(b"c")
    pool.drop(b"never there")
    assert pool.num_snapshots == 2
    assert pool.take_snapshot(b"e") == c     # the freed slot, nobody evicted
    assert pool.has_snapshot(b"a") and pool.has_snapshot(b"d")


def test_a_superseded_snapshot_is_the_first_to_go_and_stays_until_then():
    pool = StatePool(1, 3)
    a, b, c = (pool.take_snapshot(d) for d in (b"a", b"b", b"c"))
    pool.resume(b"b")
    pool.supersede(b"b")                     # its admission left a deeper one
    pool.supersede(b"never there")
    pool.supersede(None)
    assert pool.has_snapshot(b"b")           # room enough: it stays
    assert pool.take_snapshot(b"d") == b     # and goes before the older a
    assert pool.has_snapshot(b"a") and not pool.has_snapshot(b"b")


def test_a_sessions_rounds_keep_one_fresh_snapshot_among_many_sessions():
    """Sixteen sessions take turns, more rounds than there are slots: each
    round resumes from the session's last snapshot and leaves a deeper one;
    what is evicted is always an ancestor, never a session's newest."""
    sched, blocks, states = make(num_blocks=4096, max_num_seqs=4, live=6,
                                 snapshots=20)
    history = {u: list(range(1000 * u, 1000 * u + 43)) for u in range(16)}
    for round_ in range(3):
        for u, tokens in history.items():
            s = seq(f"u{u}r{round_}", tokens)
            plans = prefill(sched, s)
            assert plans[0].resumed == (round_ > 0), (u, round_)
            finish(sched, s)
            tokens.extend(range(5000 + 100 * round_, 5000 + 100 * round_ + 30))
    assert (states.resumes, states.resume_misses) == (32, 0)


def test_deepest_finds_the_deepest_snapshot_within_the_matched_blocks():
    pool = StatePool(1, 4)
    chain = [bytes([i]) for i in range(6)]
    assert pool.deepest(chain, 6) == 0
    pool.take_snapshot(chain[1]), pool.take_snapshot(chain[4])
    assert pool.deepest(chain, 6) == 5
    assert pool.deepest(chain, 4) == 2
    assert pool.deepest(chain, 1) == pool.deepest(chain[:1], 6) == 0


def test_a_prefill_leaves_one_snapshot_at_the_stride_below_its_last_token():
    sched, blocks, states = make()
    s = seq("a", range(100, 143))            # 43 tokens: chunks 32 + 11
    first, last = prefill(sched, s)
    assert (first.state_from, first.snapshot_slot) == (-1, first.state_slot)
    assert first.state_slot == s.state_slot == last.state_slot
    assert last.state_from == s.state_slot and not last.resumed
    # The last chunk starts at 32 and holds 11 tokens: the deepest multiple
    # of 8 below its last token is 8, position 40, block 10's end.
    assert (last.snapshot_len, last.cached_len) == (8, 32)
    assert last.snapshot_slot not in (0, s.state_slot)
    assert states.has_snapshot(s.prefix_chain[9])
    assert (states.num_live, states.num_snapshots) == (1, 1)


@pytest.mark.parametrize("how", ["finish", "abort", "preempt", "rollback"])
def test_the_live_slot_comes_back(how):
    sched, blocks, states = make()
    s = seq("a", range(100, 143))
    if how == "rollback":
        sched.add_seq(s)
        assert not sched.schedule().prefill_chunk.is_final
        assert states.num_live == 1
        assert sched._rollback_youngest_partial()
    else:
        prefill(sched, s)
        if how == "finish":
            finish(sched, s)
        elif how == "abort":
            assert sched.abort_seq("a") is s
        else:
            sched._preempt_youngest()
    assert s.state_slot is None and states.num_live == 0
    if how == "preempt":
        # Back through the same admission; a preemption registers no block,
        # so nothing is matched and the state starts from zeros again.
        plan = sched.schedule().prefill_chunk
        assert (plan.cached_len, plan.state_from, plan.resumed) == (
            0, -1, False)
        assert states.num_live == 1


def test_admission_is_cut_back_to_the_deepest_snapshot():
    sched, blocks, states = make()
    a = seq("a", range(100, 143))
    prefill(sched, a)
    finish(sched, a)                          # 46 tokens: 11 blocks cached
    # The next round agrees with the last prompt and not with its answer.
    b = seq("b", list(range(100, 143)) + list(range(500, 520)))
    (plan,) = prefill(sched, b)
    # The block pool matches 10 blocks (40 tokens; the 11th holds the
    # answer); the snapshot lies at 40 too: nothing is cut.
    assert (plan.cached_len, plan.resumed) == (40, True)
    assert plan.state_from not in (-1, plan.state_slot)
    assert (states.resumes, states.resume_misses) == (1, 0)
    assert states.recomputed_tokens == 0
    finish(sched, b)
    # A prompt that leaves the first one inside its 8th block: the block
    # pool matches 7 blocks, no snapshot lies that shallow: from zeros.
    hits = blocks.hit_tokens
    c = seq("c", list(range(100, 131)) + list(range(700, 705)))
    plan = prefill(sched, c)[0]
    assert (plan.cached_len, plan.state_from, plan.resumed) == (0, -1, False)
    assert plan.prefix_block_ids == []
    assert (states.resumes, states.resume_misses) == (1, 1)
    assert states.recomputed_tokens == 28
    assert blocks.hit_tokens == hits          # what was skipped: nothing


def test_a_deeper_match_is_cut_back_to_a_shallower_snapshot():
    sched, blocks, states = make()
    a = seq("a", range(100, 143))
    prefill(sched, a)
    a.block_table.extend(blocks.allocate(2))   # what its decode steps took
    finish(sched, a, answer=list(range(143, 153)))   # 53 tokens, 13 blocks
    b = seq("b", range(100, 160))             # agrees with the answer too
    hits = blocks.hit_tokens
    plans = prefill(sched, b)
    assert (plans[0].cached_len, plans[0].resumed) == (40, True)
    assert states.recomputed_tokens == 12     # blocks 11-13, prefilled again
    assert blocks.hit_tokens - hits == 40
    # The cut blocks went back to the pool: b's own hold those positions.
    assert len(b.block_table) == 15
    assert blocks.num_free_blocks == 63 - 15


@pytest.mark.parametrize("run", [1, 8])
def test_a_sessions_chunks_and_growth_go_on_after_its_last_block(run):
    """The scheduler names the taker's last block to the pool: a prompt's
    second chunk goes on after its first, and decode growth after that, a
    block a turn beside another session's.  At ``run`` 8 every full group
    of each table is eight neighbours, which the decode kernel fetches in
    one DMA; at ``run`` 1 the pool knows no runs and the tables interleave."""
    sched, blocks, _ = make(num_blocks=512, run=run)
    a, b = seq("a", range(100, 143)), seq("b", range(300, 343))
    for s in (a, b):
        assert len(prefill(sched, s)) == 2
    for _ in range(6):
        for s in (a, b):
            sched._grow(s, 1)
    for s in (a, b):
        table = s.block_table
        assert len(table) == 17
        whole = all(table[j:j + 8] == list(range(table[j], table[j] + 8))
                    for j in (0, 8))
        assert whole == (run == 8)


def test_a_snapshot_dies_with_its_block():
    sched, blocks, states = make(num_blocks=16)
    a = seq("a", range(100, 143))
    prefill(sched, a)
    finish(sched, a)
    assert states.num_snapshots == 1
    # 15 usable blocks; a prompt of 57 tokens needs all of them, so every
    # cached block of a is evicted, the snapshot's among them.
    b = seq("b", range(300, 357), max_tokens=1)
    prefill(sched, b)
    assert not states.has_snapshot(a.prefix_chain[9])
    assert states.num_snapshots == 1          # b's own
    finish(sched, b, answer=())
    c = seq("c", list(range(100, 143)) + [7, 8, 9])
    assert not prefill(sched, c)[0].resumed


def test_without_prefix_caching_no_snapshot_is_taken():
    sched, blocks, states = make(caching=False)
    a = seq("a", range(100, 143))
    plans = prefill(sched, a)
    assert all(p.snapshot_slot == p.state_slot for p in plans)
    assert (states.num_snapshots, states.snapshots_taken) == (0, 0)


def test_a_stride_that_is_no_multiple_of_the_block_is_refused():
    with pytest.raises(ValueError, match="multiple"):
        Scheduler(SchedulerConfig(), BlockPool(8, 4),
                  state_pool=StatePool(2, 2), state_stride=6)
