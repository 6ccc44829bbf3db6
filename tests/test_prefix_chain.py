"""The prefix chain: one digest (bytes, not decimal strings), one memo a
sequence (each block hashed once in its life), filled by the API server's
handler before the step thread needs it."""

import asyncio
import collections
import random

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoraServingConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.scheduler import Scheduler
from production_stack_tpu.engine.core.sequence import SamplingParams, Sequence
from production_stack_tpu.engine.kv.block_pool import (
    BlockPool,
    _chain_hash,
    extend_prefix_chain,
    prefix_block_hashes,
)

BS = 4


# -- the digest ------------------------------------------------------------


def _by_hand(tokens, bs=BS, namespace=0):
    """The chain block by block through ``_chain_hash``, every full block."""
    prev = _chain_hash(None, [namespace]) if namespace else None
    out = []
    for start in range(0, len(tokens) - bs + 1, bs):
        prev = _chain_hash(prev, tokens[start : start + bs])
        out.append(prev)
    return out


def _registered(pool):
    return set(pool._hash_to_block)


def _via_entry_point(entry, tokens, namespace):
    """The digests of the leading (len - 1) // BS blocks as each entry point
    derives them."""
    n = (len(tokens) - 1) // BS
    if entry == "prefix_block_hashes":
        return prefix_block_hashes(tokens, BS, namespace)
    pool = BlockPool(num_blocks=64, block_size=BS)
    if entry == "match_prefix":
        chain = []
        pool.match_prefix(tokens, namespace, chain=chain)
        return chain
    # register_prefix keeps what it hashed in the pool's map.
    pool.register_prefix(tokens[: n * BS], pool.allocate(n), namespace)
    digests = _by_hand(tokens[: n * BS], namespace=namespace)
    assert _registered(pool) == set(digests)
    return digests


ENTRY_POINTS = ("prefix_block_hashes", "match_prefix", "register_prefix")


@pytest.mark.parametrize("namespace", [0, 3])
@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_entry_points_agree_on_the_digest(entry, namespace):
    tokens = [(7 * i + 3) % 1000 for i in range(4 * BS + 2)]
    got = _via_entry_point(entry, tokens, namespace)
    assert got == _by_hand(tokens, namespace=namespace)[: len(got)]
    assert len(got) == 4 and all(len(d) == 16 for d in got)


CONFUSABLE = {
    "changed_token": ([1, 2, 3, 4, 5], [1, 2, 9, 4, 5]),
    "swapped_pair": ([1, 2, 3, 4, 5], [2, 1, 3, 4, 5]),
    # What a naive join of decimal digits or of minimal-width bytes runs
    # together: [1, 23] / [12, 3], and ids past 16 bits.
    "digits_run_together": ([1, 23, 5, 6, 0], [12, 3, 5, 6, 0]),
    "ids_past_16_bits": ([65536, 1, 1, 1, 0], [1, 65536, 1, 1, 0]),
    "high_half_only": ([65536 + 7, 2, 3, 4, 0], [7, 2, 3, 4, 0]),
    "zero_padding": ([0, 0, 0, 1, 0], [0, 0, 1, 0, 0]),
}


@pytest.mark.parametrize("entry", ENTRY_POINTS)
@pytest.mark.parametrize("case", sorted(CONFUSABLE))
def test_digest_tells_apart(case, entry):
    a, b = CONFUSABLE[case]
    assert (_via_entry_point(entry, a, 0) != _via_entry_point(entry, b, 0))


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_digest_tells_namespaces_apart(entry):
    tokens = list(range(1, 2 * BS + 2))
    seen = [tuple(_via_entry_point(entry, tokens, ns)) for ns in (0, 1, 2)]
    assert len(set(seen)) == 3
    # Not one block of one namespace's chain appears in another's.
    assert not set(seen[0]) & set(seen[1]) and not set(seen[1]) & set(seen[2])


def test_chain_is_bytes_of_little_endian_int32():
    """The definition, spelled out once: a later change of the packing would
    silently orphan every remote store entry."""
    import hashlib
    import struct

    tokens = [5, 70000, 2, 1]
    want = hashlib.blake2b(
        b"\x00" * 16 + struct.pack("<4i", *tokens), digest_size=16
    ).digest()
    assert _chain_hash(None, tokens) == want
    assert prefix_block_hashes(tokens + [9], BS) == [want]


def test_extend_hashes_only_what_the_chain_lacks():
    tokens = list(range(100, 100 + 6 * BS))
    chain = []
    assert extend_prefix_chain(chain, tokens, BS, 2) == 2
    head = list(chain)
    assert extend_prefix_chain(chain, tokens, BS, 2) == 0
    assert extend_prefix_chain(chain, tokens, BS, 6) == 4
    assert chain[:2] == head and chain == _by_hand(tokens)
    # Never past the tokens it is given: a short list yields full blocks only.
    short = []
    assert extend_prefix_chain(short, tokens[: BS + 1], BS, 5) == 1


def test_router_and_engine_derive_equal_keys():
    from production_stack_tpu.router.routing.kv_aware import KVAwareRouter

    tokens = [(11 * i + 1) % 503 for i in range(5 * BS + 1)]
    router = KVAwareRouter(
        tokenize=lambda text: tokens, token_block_size=BS
    )
    seq = Sequence("s", list(tokens), SamplingParams())
    BlockPool(64, BS).match_prefix(tokens, chain=seq.prefix_chain)
    assert router._prefix_hashes("x") == [d.hex() for d in seq.prefix_chain]


# -- handed over or not: the same pool, request by request -----------------


def _session_requests(seed, users=3, rounds=4):
    """A seeded multi-round session: a shared system prompt, a history a
    user that grows by question + answer each round, interleaved users."""
    rng = random.Random(seed)
    system = [rng.randrange(1000) for _ in range(3 * BS + 1)]
    history = {u: system + [rng.randrange(1000) for _ in range(rng.randrange(2, 9))]
               for u in range(users)}
    for _ in range(rounds):
        for u in rng.sample(range(users), users):
            prompt = history[u] + [rng.randrange(1000) for _ in range(rng.randrange(1, 7))]
            outputs = [rng.randrange(1000) for _ in range(rng.randrange(1, 10))]
            history[u] = prompt + outputs
            yield prompt, outputs


def _pool_state(pool):
    return (
        dict(pool._hash_to_block), dict(pool._block_to_hash),
        list(pool._cached_free), dict(pool._ref_counts), sorted(pool._free),
        pool.hit_tokens, pool.query_tokens,
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_handed_chain_gives_the_unhanded_pools_state(seed):
    """Block tables, counts and LRU order, request by request, with a small
    pool so that eviction takes part."""
    plain, handed = BlockPool(40, BS), BlockPool(40, BS)
    for prompt, outputs in _session_requests(seed):
        tables = []
        for pool, chain in ((plain, None), (handed, [])):
            if chain is not None:
                extend_prefix_chain(chain, prompt, BS, len(prompt) // BS)
            hashed = pool.chain_blocks_hashed
            blocks, cached = pool.match_prefix(prompt, chain=chain)
            if chain is not None:
                assert pool.chain_blocks_hashed == hashed  # nothing at admission
            all_ids = prompt + outputs
            need = (len(all_ids) + BS) // BS - len(blocks)
            table = blocks + pool.allocate(need)
            pool.register_prefix(all_ids, table, chain=chain)
            if chain is not None:
                # The finish hashed the blocks the outputs completed, alone.
                assert (pool.chain_blocks_hashed - hashed
                        == len(all_ids) // BS - len(prompt) // BS)
                assert chain == _by_hand(all_ids)
            pool.free(table)
            tables.append((table, cached))
        assert tables[0] == tables[1]
        assert _pool_state(plain)[:-2] == _pool_state(handed)[:-2]
        assert (plain.hit_tokens, plain.query_tokens) == (
            handed.hit_tokens, handed.query_tokens)
    assert plain.hit_tokens > 0
    assert handed.chain_blocks_hashed < plain.chain_blocks_hashed


def test_stale_chain_cannot_serve_another_prompts_block():
    """A chain with one digest flipped misses from there on: a shorter hit,
    never a block of other content."""
    pool = BlockPool(64, BS)
    cached_prompt = list(range(1, 4 * BS + 1))
    table = pool.allocate(4)
    pool.register_prefix(cached_prompt, table)
    pool.free(table)
    prompt = cached_prompt + [77]
    good = prefix_block_hashes(prompt, BS)
    blocks, cached = pool.match_prefix(prompt, chain=list(good))
    assert cached == 4 * BS
    pool.free(blocks)
    stale = list(good)
    stale[2] = bytes(b ^ 0xFF for b in stale[2])
    blocks, cached = pool.match_prefix(prompt, chain=stale)
    assert cached == 2 * BS and blocks == table[:2]


# -- the scheduler and the engine ------------------------------------------


def _scheduler(num_blocks=64, **kw):
    pool = BlockPool(num_blocks=num_blocks, block_size=BS)
    cfg = SchedulerConfig(
        max_num_seqs=4, prefill_buckets=(8, 16, 32), max_prefill_tokens=32,
        max_model_len=64, **kw,
    )
    return Scheduler(cfg, pool), pool


def _seq(seq_id, tokens, chain=False):
    s = Sequence(seq_id, list(tokens), SamplingParams(max_tokens=8))
    if chain:
        extend_prefix_chain(s.prefix_chain, s.prompt_token_ids, BS,
                            len(tokens) // BS)
    return s


@pytest.mark.parametrize("outputs,completed", [(2, 0), (3, 1), (7, 2)])
def test_step_thread_hashes_only_what_outputs_complete(outputs, completed):
    sched, pool = _scheduler()
    s = _seq("a", range(2 * BS + 1), chain=True)
    sched.add_seq(s)
    assert sched.schedule().prefill_chunk.seq is s
    assert pool.chain_blocks_hashed == 0  # nothing at admission
    s.output_token_ids.extend(range(500, 500 + outputs))
    s.block_table.extend(pool.allocate(3))
    sched.finish_seq(s)
    assert pool.chain_blocks_hashed == completed
    assert len(s.prefix_chain) == 2 + completed


def test_unhanded_sequence_hashes_once_on_the_step_thread():
    sched, pool = _scheduler()
    s = _seq("a", range(3 * BS + 2))
    sched.add_seq(s)
    sched.schedule()
    assert pool.chain_blocks_hashed == 3
    s.output_token_ids.extend([9, 9])  # completes the fourth block
    sched.finish_seq(s)
    assert pool.chain_blocks_hashed == 4  # not 3 + 4: the memo held three


@pytest.mark.parametrize("outputs,completed", [(2, 0), (7, 1)])
def test_recompute_preemption_keeps_the_memo(outputs, completed):
    sched, pool = _scheduler(mixed_batch=False)
    s = _seq("a", range(2 * BS + 1), chain=True)
    sched.add_seq(s)
    sched.schedule()
    s.output_token_ids.extend(range(700, 700 + outputs))
    memo = list(s.prefix_chain)
    sched._preempt_youngest()  # the one running sequence
    assert s.prefix_chain == memo and s.num_prompt_tokens == 2 * BS + 1 + outputs
    plan = sched.schedule()
    assert plan.prefill_chunk.seq is s
    # Re-admission hashes what the absorbed outputs completed, no more: 0
    # where they completed no block.
    assert pool.chain_blocks_hashed == completed
    assert s.prefix_chain[:2] == memo
    assert s.prefix_chain == _by_hand(s.prompt_token_ids)[: len(s.prefix_chain)]


def _engine(**kw):
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=BS, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(16, 32, 64), max_model_len=128
        ),
        **kw,
    ))


def _run(engine):
    out = collections.defaultdict(list)
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 400
        for o in engine.step():
            out[o.seq_id].append(o.new_token_id)
    return out


PROMPT_IDS = [(13 * i + 5) % 97 + 1 for i in range(5 * BS + 3)]


def test_engine_counts_handler_and_step_thread_apart():
    engine = _engine()
    params = lambda: SamplingParams(max_tokens=6)  # noqa: E731
    # Direct caller, no chain: the step thread hashes, once.
    engine.add_request("direct", prompt_token_ids=PROMPT_IDS,
                       sampling_params=params())
    direct = _run(engine)["direct"]
    s = engine.stats()
    total_blocks = (len(PROMPT_IDS) + 6 - 1) // BS  # the last token has no KV
    assert s["prefix_chain_step_blocks"] == s["prefix_chain_blocks"]
    assert s["prefix_chain_blocks"] in (total_blocks, total_blocks + 1)
    # The handler's hand-over: the step thread hashes what the outputs
    # completed and nothing at admission.
    before = s
    chain = engine.prompt_prefix_chain(PROMPT_IDS)
    assert chain == _by_hand(PROMPT_IDS)
    engine.add_request("handed", prompt_token_ids=PROMPT_IDS,
                       sampling_params=params(), prefix_chain=chain)
    assert engine.stats()["prefix_chain_step_blocks"] == before["prefix_chain_step_blocks"]
    handed = _run(engine)["handed"]
    s = engine.stats()
    assert handed == direct  # same tokens out, served from the cache
    assert s["prefix_cache_hit_tokens"] == 5 * BS
    assert (s["prefix_chain_blocks"] - before["prefix_chain_blocks"]
            == before["prefix_chain_blocks"])
    assert (s["prefix_chain_step_blocks"] - before["prefix_chain_step_blocks"]
            == before["prefix_chain_blocks"] - len(PROMPT_IDS) // BS)


def test_engine_with_a_planted_stale_chain_serves_the_same_tokens():
    engine = _engine()
    engine.add_request("a", prompt_token_ids=PROMPT_IDS,
                       sampling_params=SamplingParams(max_tokens=5))
    want = _run(engine)["a"]
    stale = engine.prompt_prefix_chain(PROMPT_IDS)
    stale[1] = bytes(16)
    engine.add_request("b", prompt_token_ids=PROMPT_IDS,
                       sampling_params=SamplingParams(max_tokens=5),
                       prefix_chain=stale)
    hit = engine.block_pool.hit_tokens
    assert _run(engine)["b"] == want
    assert engine.block_pool.hit_tokens - hit == BS  # one block, then the miss


def test_adapter_hashes_on_the_step_thread_under_its_namespace():
    import numpy as np

    from production_stack_tpu.engine.lora import TARGETS, _proj_dims

    engine = _engine(lora=LoraServingConfig(max_loras=2, max_rank=4))
    rng = np.random.default_rng(0)
    dims = _proj_dims(engine.config.model)
    engine.load_lora("one", [
        {p: (rng.standard_normal((dims[p][0], 4)).astype(np.float32) * 0.05,
             rng.standard_normal((4, dims[p][1])).astype(np.float32) * 0.05)
         for p in TARGETS}
        for _ in range(engine.config.model.num_layers)
    ], rank=4)
    # The namespace is the step thread's to resolve: the handler hashes nothing.
    assert engine.prompt_prefix_chain(PROMPT_IDS, adapter="one") is None
    assert engine.stats()["prefix_chain_blocks"] == 0
    # A chain of namespace 0 handed with an adapter is not believed.
    base_chain = engine.prompt_prefix_chain(PROMPT_IDS)
    handler_blocks = engine.prefix_chain_handler_blocks
    engine.add_request("l1", prompt_token_ids=PROMPT_IDS, adapter="one",
                       sampling_params=SamplingParams(max_tokens=3),
                       prefix_chain=list(base_chain))
    seq = engine.scheduler.waiting[0]
    ns = seq.cache_ns
    assert ns != 0 and seq.prefix_chain == []
    _run(engine)
    assert engine.prefix_chain_handler_blocks == handler_blocks
    assert engine.block_pool.chain_blocks_hashed > 0
    assert seq.prefix_chain == _by_hand(seq.all_token_ids, namespace=ns)[
        : len(seq.prefix_chain)]
    assert not set(seq.prefix_chain) & set(base_chain)
    # The adapter's blocks never match namespace 0, and match their own.
    hit = engine.block_pool.hit_tokens
    engine.add_request("base", prompt_token_ids=PROMPT_IDS,
                       sampling_params=SamplingParams(max_tokens=3),
                       prefix_chain=base_chain)
    _run(engine)
    assert engine.block_pool.hit_tokens == hit
    engine.add_request("l2", prompt_token_ids=PROMPT_IDS, adapter="one",
                       sampling_params=SamplingParams(max_tokens=3))
    _run(engine)
    assert engine.block_pool.hit_tokens - hit == 5 * BS


async def test_async_engine_hands_the_chain_to_add_request():
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    engine = AsyncEngine(EngineConfig(
        model=ModelConfig(),
        cache=CacheConfig(block_size=BS, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(16, 32, 64), max_model_len=128
        ),
    ))
    seen = {}
    inner = engine.engine.add_request

    def spy(request_id, **kw):
        seen[request_id] = list(kw["prefix_chain"])  # as handed over
        return inner(request_id, **kw)

    engine.engine.add_request = spy
    await engine.start()
    try:
        async def one(rid):
            return [ev.token_id async for ev in engine.generate(
                prompt_token_ids=list(PROMPT_IDS), request_id=rid,
                sampling_params=SamplingParams(max_tokens=6))]

        first = await asyncio.wait_for(one("r1"), 120)
        s1 = engine.stats()
        second = await asyncio.wait_for(one("r2"), 120)
        s2 = engine.stats()
    finally:
        await engine.close()
    assert first == second and len(first) == 6
    assert seen["r1"] == seen["r2"] == _by_hand(PROMPT_IDS)
    # The handler hashed the prompts; the step thread only what the outputs
    # completed (one block: 23 + 6 tokens, the last without KV).
    per_request = s1["prefix_chain_blocks"]
    assert per_request - s1["prefix_chain_step_blocks"] == len(PROMPT_IDS) // BS
    assert s1["prefix_chain_step_blocks"] <= 2
    assert s2["prefix_chain_blocks"] == 2 * per_request
    assert s2["prefix_chain_step_blocks"] == 2 * s1["prefix_chain_step_blocks"]
    assert s2["prefix_cache_hit_tokens"] == 5 * BS
