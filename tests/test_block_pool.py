"""BlockPool: allocation, refcounts, prefix-cache hash chains, LRU eviction."""

import pytest

from production_stack_tpu.engine.kv.block_pool import BlockPool


def test_basic_allocate_free():
    pool = BlockPool(num_blocks=10, block_size=4)
    assert pool.num_free_blocks == 9  # block 0 reserved
    blocks = pool.allocate(3)
    assert len(set(blocks)) == 3 and 0 not in blocks
    assert pool.num_free_blocks == 6
    pool.free(blocks)
    assert pool.num_free_blocks == 9


def test_exhaustion_raises():
    pool = BlockPool(num_blocks=4, block_size=4)
    pool.allocate(3)
    with pytest.raises(RuntimeError):
        pool.allocate(1)


def test_usage_metric():
    pool = BlockPool(num_blocks=11, block_size=4)
    pool.allocate(5)
    assert abs(pool.usage - 0.5) < 1e-9


def test_prefix_roundtrip():
    pool = BlockPool(num_blocks=20, block_size=4)
    tokens = list(range(10))  # 2 full blocks + 2 tail tokens
    blocks = pool.allocate(3)
    pool.register_prefix(tokens, blocks)
    pool.free(blocks)

    matched, cached = pool.match_prefix(tokens)
    assert cached == 8
    assert matched == blocks[:2]
    # Hit-rate metric moved.
    assert pool.prefix_hit_rate > 0


def test_prefix_leaves_one_token_uncached():
    """A fully-cached prompt must still leave >=1 token for prefill."""
    pool = BlockPool(num_blocks=20, block_size=4)
    tokens = list(range(8))  # exactly 2 blocks
    blocks = pool.allocate(2)
    pool.register_prefix(tokens, blocks)
    pool.free(blocks)
    matched, cached = pool.match_prefix(tokens)
    assert cached == 4  # only the first block: token 8-1=7 usable
    pool.free(matched)


def test_prefix_mismatch_no_hit():
    pool = BlockPool(num_blocks=20, block_size=4)
    blocks = pool.allocate(2)
    pool.register_prefix(list(range(8)), blocks)
    pool.free(blocks)
    matched, cached = pool.match_prefix([99] * 10)
    assert matched == [] and cached == 0


def test_shared_prefix_refcount():
    pool = BlockPool(num_blocks=20, block_size=4)
    tokens = list(range(12))
    blocks = pool.allocate(3)
    pool.register_prefix(tokens, blocks)
    # Two concurrent matches share the cached blocks.
    m1, _ = pool.match_prefix(tokens)
    m2, _ = pool.match_prefix(tokens)
    assert m1 == m2
    pool.free(m1)
    # Still referenced by m2 + original: freeing once must not reclaim.
    free_before = pool.num_free_blocks
    m3, cached = pool.match_prefix(tokens)
    assert cached > 0
    assert pool.num_free_blocks == free_before


def test_lru_eviction_of_cached_blocks():
    pool = BlockPool(num_blocks=6, block_size=4, enable_prefix_caching=True)
    tokens_a = list(range(100, 108))
    blocks_a = pool.allocate(2)
    pool.register_prefix(tokens_a, blocks_a)
    pool.free(blocks_a)
    assert pool.num_free_blocks == 5
    # Allocate everything: cached blocks get evicted last (LRU).
    blocks_b = pool.allocate(5)
    assert pool.num_free_blocks == 0
    # The cache entry for A must be gone.
    matched, cached = pool.match_prefix(tokens_a)
    assert cached == 0
    pool.free(blocks_b)


def test_disabled_prefix_caching():
    pool = BlockPool(num_blocks=10, block_size=4, enable_prefix_caching=False)
    blocks = pool.allocate(2)
    pool.register_prefix(list(range(8)), blocks)
    pool.free(blocks)
    matched, cached = pool.match_prefix(list(range(8)))
    assert matched == [] and cached == 0


# -- the sequence's chain handed in (kv/block_pool.py: extend_prefix_chain) --


@pytest.mark.parametrize("handed", [False, True])
def test_prefix_roundtrip_with_chain(handed):
    """The chain a sequence keeps gives the hit of the unhanded call, and
    holds afterwards what was hashed for it."""
    pool = BlockPool(num_blocks=20, block_size=4)
    tokens = list(range(10))
    blocks = pool.allocate(3)
    chain = [] if handed else None
    pool.register_prefix(tokens, blocks, chain=chain)
    pool.free(blocks)
    hashed = pool.chain_blocks_hashed
    assert hashed == 2
    matched, cached = pool.match_prefix(tokens, chain=chain)
    assert (matched, cached) == (blocks[:2], 8)
    # With the memo the admission hashed nothing; without, both blocks again.
    assert pool.chain_blocks_hashed - hashed == (0 if handed else 2)
    if handed:
        assert len(chain) == 2


def test_chain_shorter_than_the_prompt_is_extended_in_place():
    pool = BlockPool(num_blocks=20, block_size=4)
    tokens = list(range(13))  # 3 full blocks, 1 token left to prefill
    blocks = pool.allocate(4)
    pool.register_prefix(tokens, blocks)
    pool.free(blocks)
    chain = []
    pool.match_prefix(tokens[:5], chain=chain)  # the first block only
    assert len(chain) == 1
    hashed = pool.chain_blocks_hashed
    matched, cached = pool.match_prefix(tokens, chain=chain)
    assert cached == 12 and matched == blocks[:3]
    assert len(chain) == 3 and pool.chain_blocks_hashed - hashed == 2


def test_chain_longer_than_usable_leaves_one_token_to_prefill():
    """A memo that already holds the digest of the prompt's last full block
    (the handler hashes every full block) must not turn an exact-multiple
    prompt into a full hit."""
    pool = BlockPool(num_blocks=20, block_size=4)
    tokens = list(range(8))
    blocks = pool.allocate(2)
    chain = []
    pool.register_prefix(tokens, blocks, chain=chain)
    pool.free(blocks)
    assert len(chain) == 2
    matched, cached = pool.match_prefix(tokens, chain=chain)
    assert cached == 4 and matched == blocks[:1]


def test_prefix_caching_off_hashes_nothing():
    pool = BlockPool(num_blocks=20, block_size=4, enable_prefix_caching=False)
    chain = []
    blocks = pool.allocate(2)
    pool.register_prefix(list(range(8)), blocks, chain=chain)
    assert pool.match_prefix(list(range(9)), chain=chain) == ([], 0)
    assert chain == [] and pool.chain_blocks_hashed == 0


# -- which block a taker gets (ascending ids; runs at ``run`` > 1) -----------

import random
from collections import OrderedDict

import numpy as np

from production_stack_tpu.engine.kv.block_pool import _chain_hash


def _is_run(blocks):
    return all(b == blocks[0] + i for i, b in enumerate(blocks))


@pytest.mark.parametrize("run", [1, 8])
def test_a_fresh_pool_hands_out_ascending_neighbours(run):
    pool = BlockPool(num_blocks=4096, block_size=16, run=run)
    first = pool.allocate(128)          # a 2,048-token prefill chunk
    assert _is_run(first)
    assert first[0] == (1 if run == 1 else run)
    # The next chunk of the same prompt goes on where the first ended.
    second = pool.allocate(128, after=first[-1])
    assert _is_run(first + second)
    # Freed plain blocks come back last in, first out at run 1.
    pool.free(second[-3:])
    if run == 1:
        assert pool.allocate(3) == second[-3:][::-1]


def test_sixteen_rows_grown_a_block_a_turn_each_keep_their_runs():
    """A decode pass gives every running row one block, in turn: with
    ``after=`` a row fills the group it started and then starts the next
    wholly free one, so every group of its table is eight neighbours --
    what the decode kernel fetches in one DMA, by its own rule."""
    from production_stack_tpu.engine.ops.pallas.paged_attention import (
        whole_groups,
    )

    pool = BlockPool(num_blocks=8192, block_size=16, run=8)
    rows = [[] for _ in range(16)]
    for _ in range(40):
        for table in rows:
            table.extend(pool.allocate(1, after=table[-1] if table else None))
    tables = np.array(rows, np.int32)
    assert tables.shape == (16, 40)
    assert whole_groups(tables, 8, xp=np).all()
    assert len(set(tables.ravel().tolist())) == 16 * 40


def test_rows_grown_a_block_a_turn_interleave_at_run_1():
    """No groups, ``after`` ignored: what the pool did before runs, in
    ascending order (row i holds every sixteenth block)."""
    pool = BlockPool(num_blocks=8192, block_size=16, run=1)
    rows = [[] for _ in range(16)]
    for _ in range(5):
        for table in rows:
            table.extend(pool.allocate(1, after=table[-1] if table else None))
    assert rows == [[1 + i + 16 * t for t in range(5)] for i in range(16)]


def test_a_broken_groups_rest_is_taken_only_when_no_whole_group_is_left():
    pool = BlockPool(num_blocks=33, block_size=16, run=8)   # groups 1..3 whole
    a = pool.allocate(3)                                    # breaks one group
    assert a[0] % 8 == 0 and _is_run(a)
    # Plain takers draw the two whole groups' heads first ...
    b, c = pool.allocate(1), pool.allocate(1)
    heads = {8, 16, 24}
    assert {a[0], b[0], c[0]} == heads
    # ... and only then the rests (the null block's group, 1..7, among
    # them), last freed first; nothing was reserved for anybody.
    rest = pool.allocate(pool.num_free_blocks)
    assert sorted(a + b + c + rest) == list(range(1, 33))
    with pytest.raises(RuntimeError):
        pool.allocate(1)


def test_a_run_that_meets_a_taken_block_starts_at_a_whole_groups_head():
    pool = BlockPool(num_blocks=256, block_size=16, run=8)
    row = pool.allocate(8)                      # one whole group
    # Nothing is reserved: somebody else takes the id after the row's last.
    blocker = pool.allocate(1, after=row[-1])
    assert blocker == [row[-1] + 1]
    more = pool.allocate(2, after=row[-1])
    assert more[0] % 8 == 0 and _is_run(more) and more[0] != blocker[0]
    # A freed group is whole again and is handed out after the fresh ones.
    pool.free(row)
    seen = []
    while pool._whole:
        seen.append(pool.allocate(1)[0] // 8)
    assert seen[-1] == row[0] // 8


class _OldPool:
    """The pool as it was before runs (PR 53), what matters of it: a list
    of free ids taken from its end, refcounts, the cached-free LRU."""

    def __init__(self, num_blocks, block_size):
        self.block_size = block_size
        self.num_blocks = num_blocks
        self._free = list(range(1, num_blocks))
        self._ref_counts = {}
        self._hash_to_block, self._block_to_hash = {}, {}
        self._cached_free = OrderedDict()
        self.evicted = []

    @property
    def num_free_blocks(self):
        return len(self._free) + len(self._cached_free)

    def allocate(self, n):
        if self.num_free_blocks < n:
            raise RuntimeError("exhausted")
        out = []
        for _ in range(n):
            if self._free:
                block = self._free.pop()
            else:
                block, _ = self._cached_free.popitem(last=False)
                self._evict_hash(block)
            self._ref_counts[block] = 1
            out.append(block)
        return out

    def free(self, blocks):
        for block in blocks:
            refs = self._ref_counts.get(block, 0) - 1
            if refs > 0:
                self._ref_counts[block] = refs
                continue
            self._ref_counts.pop(block, None)
            if block in self._block_to_hash:
                self._cached_free[block] = None
                self._cached_free.move_to_end(block)
            else:
                self._free.append(block)

    def _evict_hash(self, block):
        digest = self._block_to_hash.pop(block, None)
        if digest is not None and self._hash_to_block.get(digest) == block:
            del self._hash_to_block[digest]
            self.evicted.append(digest)

    def match(self, digests):
        blocks = []
        for d in digests:
            block = self._hash_to_block.get(d)
            if block is None:
                break
            blocks.append(block)
        for block in blocks:
            if block in self._cached_free:
                del self._cached_free[block]
                self._ref_counts[block] = 1
            else:
                self._ref_counts[block] = self._ref_counts.get(block, 0) + 1
        return blocks

    def register(self, digests, table):
        for d, block in zip(digests, table):
            if d not in self._hash_to_block:
                self._evict_hash(block)
                self._hash_to_block[d] = block
                self._block_to_hash[block] = d


@pytest.mark.parametrize("run", [1, 8])
def test_ten_thousand_operations_leave_the_old_pools_book(run):
    """Which id a taker gets changed; nothing else did.  A random sequence
    of admissions (match + allocate), growth, finishes (register + free)
    and aborts against the pool as it was: free and cached counts, usage,
    refcounts, what the prefix cache holds, the cached-free tier's LRU
    order and every eviction (``on_evict``) in order read the same, and
    both refuse the same allocation."""
    rng = random.Random(run)
    BS, N = 4, 96
    new, old = BlockPool(N, BS, run=run), _OldPool(N, BS)
    evicted = []
    new.on_evict = evicted.append
    prompts = [[rng.randrange(50) for _ in range(rng.randrange(4, 60))]
               for _ in range(24)]
    live = []       # (tokens, new table, old table)
    refused = 0
    for _ in range(10_000):
        op = rng.random()
        if op < 0.35 or not live:
            tokens = list(rng.choice(prompts)) + [
                rng.randrange(50) for _ in range(rng.randrange(0, 6))]
            digests, prev = [], None
            for i in range((len(tokens) - 1) // BS):
                prev = _chain_hash(prev, tokens[i * BS:(i + 1) * BS])
                digests.append(prev)
            t_new, cached = new.match_prefix(tokens)
            t_old = old.match(digests)
            assert len(t_new) == len(t_old) and cached == len(t_old) * BS
            need = -(-len(tokens) // BS) - len(t_new)
            if new.can_allocate(need):
                t_new = t_new + new.allocate(
                    need, after=t_new[-1] if t_new else None)
                t_old = t_old + old.allocate(need)
                live.append((tokens, t_new, t_old))
            else:
                refused += 1
                with pytest.raises(RuntimeError):
                    new.allocate(need)
                with pytest.raises(RuntimeError):
                    old.allocate(need)
                new.free(t_new)
                old.free(t_old)
        elif op < 0.7:
            tokens, t_new, t_old = rng.choice(live)
            tokens.extend(rng.randrange(50) for _ in range(BS))
            if new.can_allocate(1):
                t_new.extend(new.allocate(1, after=t_new[-1]))
                t_old.extend(old.allocate(1))
        else:
            tokens, t_new, t_old = live.pop(rng.randrange(len(live)))
            if op < 0.95:                       # a finish; else an abort
                new.register_prefix(tokens, t_new)
                digests, prev = [], None
                for i in range(len(tokens) // BS):
                    prev = _chain_hash(prev, tokens[i * BS:(i + 1) * BS])
                    digests.append(prev)
                old.register(digests, t_old)
            new.free(t_new)
            old.free(t_old)
        assert new.num_free_blocks == old.num_free_blocks
        assert len(new._free) == len(old._free)
        assert sorted(new._ref_counts.values()) == sorted(
            old._ref_counts.values())
        assert set(new._hash_to_block) == set(old._hash_to_block)
        assert [new._block_to_hash[b] for b in new._cached_free] == [
            old._block_to_hash[b] for b in old._cached_free]
        assert evicted == old.evicted
        if run > 1:
            # The groups' book agrees with the free store.
            counts = [0] * len(new._group_free)
            for b in new._free:
                counts[b // run] += 1
            assert counts == new._group_free
            assert set(new._whole) == {
                g for g, c in enumerate(counts) if c == run}
    assert abs(new.usage - (N - 1 - old.num_free_blocks) / (N - 1)) < 1e-12
    assert refused and evicted and len(evicted) > 50


def test_exhaustion_raises_at_the_same_count_with_runs():
    pool = BlockPool(num_blocks=30, block_size=4, run=8)
    held = [pool.allocate(1, after=None)[0] for _ in range(29)]
    assert sorted(held) == list(range(1, 30))
    with pytest.raises(RuntimeError):
        pool.allocate(1)


def test_a_half_million_block_pool_lists_itself_in_tens_of_milliseconds():
    """Cell 6's pool (jamba2-3b on a v5e): the boot pays this once."""
    import time

    t0 = time.perf_counter()
    pool = BlockPool(num_blocks=525_184, block_size=16, run=8)
    took = time.perf_counter() - t0
    assert pool.num_free_blocks == 525_183
    assert took < 1.0, took     # ~0.06 s alone; a loaded test machine: slack
