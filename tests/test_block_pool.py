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
