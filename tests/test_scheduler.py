"""Scheduler: admission, bucketing, block accounting, preemption."""

import pytest

from production_stack_tpu.engine.config import SchedulerConfig
from production_stack_tpu.engine.core.scheduler import (
    PREFILL_DISPATCH_SLOTS,
    Scheduler,
    cover_prefill,
)
from production_stack_tpu.engine.core.sequence import SamplingParams, Sequence
from production_stack_tpu.engine.kv.block_pool import BlockPool


def make_scheduler(num_blocks=64, max_num_seqs=4, offload_cb=None, **kw):
    pool = BlockPool(num_blocks=num_blocks, block_size=4)
    cfg = SchedulerConfig(
        max_num_seqs=max_num_seqs,
        prefill_buckets=(8, 16, 32),
        max_prefill_tokens=32,
        max_model_len=64,
        **kw,
    )
    return Scheduler(cfg, pool, offload_cb=offload_cb), pool


def seq(seq_id, n_tokens, t=0.0, max_tokens=4):
    s = Sequence(
        seq_id=seq_id,
        prompt_token_ids=list(range(n_tokens)),
        sampling_params=SamplingParams(max_tokens=max_tokens),
    )
    s.arrival_time = t
    return s


def test_prefill_scheduled_first():
    sched, pool = make_scheduler()
    sched.add_seq(seq("a", 6))
    plan = sched.schedule()
    assert plan.prefill_chunk is not None
    assert plan.prefill_chunk.bucket_len == 8
    assert plan.prefill_chunk.num_new_tokens == 6
    assert len(plan.prefill_chunk.new_block_ids) == 2  # ceil(6/4)
    assert sched.num_running == 1


def test_decode_after_prefill():
    sched, pool = make_scheduler()
    sched.add_seq(seq("a", 6))
    sched.schedule()  # prefill
    sched.running[0].output_token_ids.append(1)  # sampled first token
    plan = sched.schedule()
    assert plan.decode is not None
    assert [s.seq_id for s in plan.decode.seqs] == ["a"]


def test_decode_extends_block_table_when_needed():
    sched, pool = make_scheduler()
    s = seq("a", 8)  # exactly 2 blocks
    sched.add_seq(s)
    sched.schedule()
    s.output_token_ids.append(1)  # num_tokens=9 > 8 slots
    before = len(s.block_table)
    plan = sched.schedule()
    assert plan.decode is not None
    assert len(s.block_table) == before + 1


def test_prefill_admission_respects_batch_cap():
    # Alternating (mixed_batch=False) semantics; the fused path's
    # admission behavior is covered in test_mixed_batch.py.
    sched, pool = make_scheduler(max_num_seqs=2, mixed_batch=False)
    for i in range(3):
        sched.add_seq(seq(f"s{i}", 4))
    assert sched.schedule().prefill_chunk is not None
    assert sched.schedule().prefill_chunk is not None
    # Batch full: third stays waiting, decode is scheduled instead.
    for s in sched.running:
        s.output_token_ids.append(1)
    plan = sched.schedule()
    assert plan.prefill_chunk is None and plan.decode is not None
    assert sched.num_waiting == 1


def test_preemption_when_pool_exhausted():
    offloaded = []
    sched, pool = make_scheduler(
        num_blocks=7,  # 6 usable
        max_num_seqs=2,
        mixed_batch=False,  # alternating semantics under test
        offload_cb=lambda s, blocks: offloaded.append(s.seq_id) or True,
    )
    s1 = seq("old", 8, t=1.0)  # 2 blocks
    s2 = seq("young", 8, t=2.0)  # 2 blocks
    sched.add_seq(s1)
    sched.add_seq(s2)
    assert sched.schedule().prefill_chunk.seq is s1
    assert sched.schedule().prefill_chunk.seq is s2
    # Fill the pool so decode growth must preempt.
    pool.allocate(pool.num_free_blocks)
    s1.output_token_ids.append(1)  # needs block
    s2.output_token_ids.append(1)  # needs block
    plan = sched.schedule()
    assert plan.decode is not None
    assert [s.seq_id for s in plan.decode.seqs] == ["old"]
    assert offloaded == ["young"]
    assert sched.preempted[0].seq_id == "young"
    assert sched.preempted[0].offloaded


def test_preempted_resumes_before_waiting():
    sched, pool = make_scheduler()
    s1 = seq("preempted", 8)
    s1.status = s1.status.PREEMPTED
    sched.preempted.append(s1)
    sched.add_seq(seq("fresh", 8))
    plan = sched.schedule()
    assert plan.prefill_chunk.seq is s1


def test_finish_registers_prefix_and_frees():
    sched, pool = make_scheduler()
    s = seq("a", 8)
    sched.add_seq(s)
    sched.schedule()
    free_before_finish = pool.num_free_blocks
    sched.finish_seq(s)
    assert pool.num_free_blocks > free_before_finish
    # Prefix reusable by an identical prompt.
    matched, cached = pool.match_prefix(list(range(8)))
    assert cached == 4


def test_abort_releases_blocks():
    sched, pool = make_scheduler()
    s = seq("a", 8)
    sched.add_seq(s)
    sched.schedule()
    used = pool.num_free_blocks
    assert sched.abort_seq("a") is s
    assert pool.num_free_blocks > used
    assert sched.num_running == 0


def test_priority_jumps_waiting_queue():
    """vLLM priority semantics: lower value runs earlier; equal
    priorities keep FCFS order."""
    from production_stack_tpu.engine.core.sequence import (
        SamplingParams,
        Sequence,
    )

    sched, _pool = make_scheduler(max_num_seqs=2)
    for i, prio in enumerate([0, 0, -1, 5, -1]):
        sched.add_seq(Sequence(
            seq_id=f"r{i}", prompt_token_ids=[1, 2, 3],
            sampling_params=SamplingParams(max_tokens=4, priority=prio),
        ))
    order = [s.seq_id for s in sched.waiting]
    # -1s first (FCFS among them), then the 0s, then the 5.
    assert order == ["r2", "r4", "r0", "r1", "r3"]


def test_preemption_evicts_lowest_priority_running():
    """Pool pressure evicts the highest-value (lowest-priority) running
    sequence, not simply the youngest."""
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    engine = LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=20,
                          host_offload_gb=0.25),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128,
        ),
    ))
    # Two ~28-token prompts fill 14 of 19 usable blocks; decode growth
    # forces a preemption.  The LOW-priority (higher value) sequence must
    # be the victim even though it is OLDER.
    engine.add_request("low", prompt="alpha bravo charlie forever",
                       sampling_params=SamplingParams(max_tokens=16,
                                                      priority=7))
    engine.add_request("high", prompt="delta echo foxtrot forevers",
                       sampling_params=SamplingParams(max_tokens=16,
                                                      priority=-7))
    low_seq = engine._seqs["low"]
    victims = []
    orig_preempt = engine.scheduler._preempt_youngest

    def spy():
        victims.append(max(
            engine.scheduler.running,
            key=lambda s: (s.sampling_params.priority, s._admit_idx),
        ).seq_id)
        orig_preempt()

    engine.scheduler._preempt_youngest = spy
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 2000
        engine.step()
    assert engine.scheduler.num_preemptions > 0
    # The first (and decisive) victim is the low-priority sequence, even
    # though it is OLDER; the tiny pool may ping-pong later, but priority
    # decided who lost the capacity race.
    assert victims[0] == "low"
    assert low_seq.preempt_count > 0


def test_spec_window_budget_covers_max_acceptance():
    """With speculation fused into the window, a pure-decode plan's
    per-row TOKEN budget (and the block pre-allocation backing it) must
    cover the max-acceptance growth K x (ngram + 1), clamped by
    max_model_len / max_tokens room."""
    sched, pool = make_scheduler(
        num_blocks=128, decode_window=4, speculative_ngram=3,
    )
    s = seq("a", 6, max_tokens=40)
    sched.add_seq(s)
    sched.schedule()  # prefill (6 tokens -> 2 blocks)
    s.output_token_ids.append(1)
    plan = sched.schedule()
    assert plan.decode is not None and plan.decode_window == 4
    # 4 iterations x (3 drafts + 1 committed) = 16-token budget.
    assert plan.decode.steps == [16]
    # Blocks cover slots through num_tokens + budget - 1 = 7 + 16 - 1
    # = 22 slots -> ceil(22/4) = 6 blocks.
    assert len(s.block_table) == 6


def test_spec_window_budget_clamped_by_room():
    """The max-acceptance budget still respects max_tokens room: a
    request 3 tokens from its cap gets a 3-token budget, not 16."""
    sched, pool = make_scheduler(
        num_blocks=128, decode_window=4, speculative_ngram=3,
    )
    s = seq("a", 6, max_tokens=4)
    sched.add_seq(s)
    sched.schedule()
    s.output_token_ids.append(1)
    plan = sched.schedule()
    assert plan.decode.steps == [3]


def test_provisional_spec_window_budgets_optimistically():
    """Chained windows plan under full-acceptance optimism: the next
    window's budget and block growth assume the in-flight window lands
    its whole token budget."""
    sched, pool = make_scheduler(
        num_blocks=128, decode_window=4, speculative_ngram=3,
    )
    s = seq("a", 6, max_tokens=60)  # max_model_len is 64 (make_scheduler)
    sched.add_seq(s)
    sched.schedule()
    s.output_token_ids.append(1)
    plan = sched.schedule()
    assert plan.decode.steps == [16]
    nxt = sched.schedule_provisional_window(plan.decode.seqs, plan.decode.steps)
    assert nxt is not None and nxt.provisional
    # Optimistic base = 7 + 16 = 23 tokens; room to max_model_len=64
    # leaves >= 16, so the full spec budget applies again.
    assert nxt.decode.steps == [16]
    # Table covers 23 + 16 - 1 = 38 slots -> ceil(38/4) = 10 blocks.
    assert len(s.block_table) == 10


def test_spec_budget_not_inflated_for_sampled_batches():
    """The fused drafter only engages for all-greedy batches, so a
    batch with a sampled row keeps the plain K-token window budget —
    no blocks pre-allocated for drafts that cannot happen."""
    sched, pool = make_scheduler(
        num_blocks=128, decode_window=4, speculative_ngram=3,
    )
    g = seq("g", 6, max_tokens=40)
    s = Sequence(
        seq_id="s",
        prompt_token_ids=list(range(6)),
        sampling_params=SamplingParams(max_tokens=40, temperature=0.9),
    )
    sched.add_seq(g)
    sched.add_seq(s)
    sched.schedule()
    sched.schedule()  # both prefills
    g.output_token_ids.append(1)
    s.output_token_ids.append(1)
    plan = sched.schedule()
    assert plan.decode is not None
    assert plan.decode.steps == [4, 4]


# -- the cover: which prefill programs a prompt's new tokens run ----------

DEFAULT_BUCKETS = SchedulerConfig().prefill_buckets  # 128 ... 2048

COVERS = [
    # The benchmark's bucket set: whole 256-slot chunks while they cost
    # less than the one 2,048-slot program, dispatches counted.
    (1, (256, 2048), (256,)),
    (256, (256, 2048), (256,)),
    (257, (256, 2048), (256, 256)),
    (600, (256, 2048), (256, 256, 256)),
    (1024, (256, 2048), (256,) * 4),
    (1280, (256, 2048), (256,) * 5),
    # Six 256-slot dispatches would hold 1,500 tokens in fewer slots, and
    # take longer: the warm-up's long prompt loads the big program.
    (1281, (256, 2048), (2048,)),
    (1500, (256, 2048), (2048,)),
    (2048, (256, 2048), (2048,)),
    # Above the largest bucket: the old chunked prefill is the same rule.
    (2049, (256, 2048), (2048, 256)),
    (2600, (256, 2048), (2048, 256, 256, 256)),
    (5000, (256, 2048), (2048, 2048, 256, 256, 256, 256)),
    # The default set is dense: a cover wins only by a small second chunk.
    (100, DEFAULT_BUCKETS, (128,)),
    (300, DEFAULT_BUCKETS, (512,)),
    (512, DEFAULT_BUCKETS, (512,)),
    (513, DEFAULT_BUCKETS, (512, 128)),
    (600, DEFAULT_BUCKETS, (512, 128)),
    (700, DEFAULT_BUCKETS, (512, 256)),
    (800, DEFAULT_BUCKETS, (1024,)),
    (1100, DEFAULT_BUCKETS, (1024, 128)),
    (1400, DEFAULT_BUCKETS, (1024, 512)),
    (1600, DEFAULT_BUCKETS, (1024, 512, 128)),
    (1700, DEFAULT_BUCKETS, (2048,)),
    (2100, DEFAULT_BUCKETS, (2048, 128)),
    (4100, DEFAULT_BUCKETS, (2048, 2048, 128)),
    # One bucket: nothing to choose.
    (7, (64,), (64,)),
    (64, (64,), (64,)),
    (65, (64,), (64, 64)),
    (200, (64,), (64, 64, 64, 64)),
    # Small buckets (the tests' engines): a dispatch outweighs any padding.
    (20, (16, 32, 64), (32,)),
    (40, (16, 32, 64), (64,)),
    (100, (16, 32), (32, 32, 32, 16)),
]


@pytest.mark.parametrize("num_new,buckets,run", COVERS)
def test_cover_prefill_picks_the_cheapest_run(num_new, buckets, run):
    assert cover_prefill(num_new, buckets) == run
    # Every chunk but the last is full, the last holds the remainder in
    # the smallest bucket that does, and no program is invented.
    assert set(run) <= set(buckets)
    rest = num_new - sum(run[:-1])
    assert 0 < rest <= run[-1]
    assert run[-1] == min(b for b in buckets if b >= rest)
    # What a chunk leaves is covered by the rest of the same run: a plan
    # made chunk by chunk is the plan made at the start.
    if len(run) > 1:
        assert cover_prefill(num_new - run[0], buckets) == run[1:]


@pytest.mark.parametrize("buckets", [(256, 2048), DEFAULT_BUCKETS, (48, 112)])
def test_cover_prefill_is_pure_and_never_beaten(buckets):
    """Same inputs, same plan, whatever was asked before (lockstep replicas
    plan alike); and no other run of full chunks and a last one is cheaper."""
    def cost(run):
        return sum(run) + PREFILL_DISPATCH_SLOTS * len(run)

    def runs(n, depth):
        for b in buckets:
            if b >= n:
                yield (b,)
            elif depth > 1:
                for rest in runs(n - b, depth - 1):
                    yield (b,) + rest

    lengths = list(range(1, 3 * max(buckets), 37))
    first = [cover_prefill(n, buckets) for n in lengths]
    cover_prefill.cache_clear()
    again = [cover_prefill(n, buckets) for n in reversed(lengths)]
    assert first == again[::-1]
    for n, run in zip(lengths[:40], first):
        best = min((cost(r), len(r)) for r in runs(n, len(run) + 1))
        assert (cost(run), len(run)) == best, (n, run)


def make_cover_scheduler(num_blocks=256, **kw):
    pool = BlockPool(num_blocks=num_blocks, block_size=16)
    cfg = SchedulerConfig(
        max_num_seqs=4, prefill_buckets=(256, 2048), max_model_len=4096,
        mixed_batch=False, **kw,
    )
    return Scheduler(cfg, pool), pool


def test_schedule_runs_a_cover_chunk_by_chunk():
    sched, pool = make_cover_scheduler()
    s = seq("a", 600)
    sched.add_seq(s)
    plans = [sched.schedule().prefill_chunk for _ in range(3)]
    assert [p.bucket_len for p in plans] == [256, 256, 256]
    assert [p.num_new_tokens for p in plans] == [256, 256, 88]
    assert [p.cached_len for p in plans] == [0, 256, 512]
    assert [p.is_final for p in plans] == [False, False, True]
    assert [p.cover for p in plans] == [(256,) * 3, (256,) * 2, (256,)]
    # Each chunk reads the earlier ones as its cached prefix.
    assert plans[2].prefix_block_ids == (
        plans[0].new_block_ids + plans[1].new_block_ids
    )
    assert not s.partial_prefill and sched.running == [s]
    assert len(s.block_table) == -(-600 // 16)


def test_a_prefix_hit_is_followed_by_a_cover_of_what_is_new():
    sched, pool = make_cover_scheduler()
    old = seq("old", 1024)
    sched.add_seq(old)
    while sched.waiting:
        sched.schedule()
    sched.finish_seq(old)
    # Same first 1,024 tokens, 700 new ones: three chunks behind the hit.
    new = seq("new", 1724)
    sched.add_seq(new)
    plans = [sched.schedule().prefill_chunk for _ in range(3)]
    assert [p.cached_len for p in plans] == [1024, 1280, 1536]
    assert [p.num_new_tokens for p in plans] == [256, 256, 188]
    assert plans[0].cover == (256, 256, 256)
    assert len(plans[0].prefix_block_ids) == 1024 // 16
    assert plans[2].is_final and sched.running == [new]


@pytest.mark.parametrize("how", ["abort", "rollback", "preemption"])
def test_a_cover_cut_short_frees_every_block(how):
    """A sequence between two chunks of its cover sits in the waiting queue
    holding blocks: abort, a rollback under pool pressure and the
    preemption of a decoder beside it all hand every block back."""
    sched, pool = make_cover_scheduler(num_blocks=64)  # 63 usable
    free = pool.num_free_blocks
    if how == "preemption":
        short = seq("short", 250, max_tokens=64)
        sched.add_seq(short)
        assert sched.schedule().prefill_chunk.is_final
        short.output_token_ids.append(1)
    long = seq("long", 700)
    sched.add_seq(long)
    first = sched.schedule().prefill_chunk
    assert first.cover == (256, 256, 256) and long.partial_prefill
    assert pool.num_free_blocks < free
    if how == "abort":
        assert sched.abort_seq("long") is long
    elif how == "rollback":
        held = pool.allocate(pool.num_free_blocks)  # someone fills the pool
        # No second chunk fits and nothing decodes: the cover is rolled
        # back and starts over in its own blocks, from the same plan.
        again = sched.schedule().prefill_chunk
        assert again.cover == first.cover and again.cached_len == 0
        assert len(long.block_table) == 16
        pool.free(held)
        assert sched.abort_seq("long") is long
    else:
        # The decoder cannot grow and the cover cannot go on: the decoder
        # is preempted, the cover rolled back, and the decoder comes back
        # first, its 257 tokens a prompt under the same rule.
        held = pool.allocate(pool.num_free_blocks)
        short.output_token_ids.extend([1] * 6)  # a 17th block
        plan = sched.schedule()
        assert sched.num_preemptions == 1
        assert long.block_table == [] and not long.partial_prefill
        assert plan.prefill_chunk.seq is short
        assert plan.prefill_chunk.cover == (256, 256)
        pool.free(held)
        while sched.num_waiting:
            assert not sched.schedule().is_empty
        for s in (short, long):
            sched.finish_seq(s)
    assert pool.num_free_blocks == free
    assert not sched.has_unfinished()
