"""KV block pool with hash-chain prefix caching.

vLLM-style paged KV management rebuilt for the TPU engine: fixed-size token
blocks, ref-counted sharing of cached prefixes, and LRU eviction of
freed-but-cached blocks.  The prefix-cache hit rate measured here feeds the
``tpu:prefix_cache_hit_rate`` gauge the router's KV-aware routing and the
Grafana dashboard key off (reference scrapes the same concept from vLLM as
``vllm:gpu_prefix_cache_hit_rate``, stats/engine_stats.py:52-53).

Block 0 is the reserved *null block*: padding scatter targets land there and
it is never read or allocated.

**Which block a taker gets.**  Ids are handed out ascending: ``allocate(n)``
from a fresh pool returns ``b, b+1, ..., b+n-1``, because pages that lie next
to each other in the pool travel in one DMA of the paged decode walk where a
page is small (``ops/pallas/paged_attention.py: blocks_per_descriptor``; the
engine hands its ``R`` in as ``run``).  At ``run > 1`` the pool also keeps a
growing row's run together: ``allocate(n, after=b)`` takes ``b+1, b+2, ...``
while they are free, and a taker that cannot go on (no ``after``, or the next
id is taken) starts at the head of an aligned group of ``run`` ids that is
wholly free and leaves that group's rest for its own continuation.  Plain
takers draw from wholly free groups before they draw from a broken group's
rest; a fresh pool's groups are taken in ascending order, and a group that
frees made whole again waits behind them.
Nothing is reserved: every free block is any taker's, counts, eviction and
the prefix cache do not know about runs, and a block evicted from the
cached-free tier simply breaks a run (the kernel fetches that group page by
page).  At ``run == 1`` there are no groups, ``after`` is ignored and freed
blocks are reused last-in-first-out.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_NO_PREV = b"\x00" * 16


def _pack(token_ids: Sequence[int]) -> bytes:
    """Token ids as little-endian int32, packed in one go: fixed-width ids
    cannot run into each other (``[1, 23]`` / ``[12, 3]``), and no
    per-token object is made."""
    return np.asarray(token_ids, "<i4").tobytes()


def _block_digest(prev: bytes, ids: bytes) -> bytes:
    """THE digest of one block: blake2b-128 over the previous block's digest
    (16 zero bytes at the head of a chain) and the block's packed ids."""
    return hashlib.blake2b(prev + ids, digest_size=16).digest()


def _chain_hash(prev: Optional[bytes], tokens: Sequence[int]) -> bytes:
    """One block's digest from its ids (tests and tools; the served path
    packs a sequence once, ``extend_prefix_chain``)."""
    return _block_digest(prev or _NO_PREV, _pack(tokens))


def _namespace_seed(namespace: int) -> Optional[bytes]:
    """Seed the hash chain per namespace (e.g. LoRA adapter slot): KV
    computed under one adapter must never be served to another.
    Namespace 0 keeps the unseeded chain."""
    return _chain_hash(None, [namespace]) if namespace else None


def extend_prefix_chain(
    chain: List[bytes],
    token_ids: Sequence[int],
    block_size: int,
    num_blocks: int,
    namespace: int = 0,
) -> int:
    """Grow ``chain`` — the digests of the leading full blocks of
    ``token_ids`` under ``namespace`` — to ``num_blocks`` entries, hashing
    only the blocks it lacks, and return how many that was.  The missing
    ids are packed once and each block is a slice of that buffer.  Tokens
    only ever append to a sequence, so an entry once made stays true: a
    sequence keeps one chain for its life (``Sequence.prefix_chain``) and
    every reader of the chain — match, register, the remote tiers, the
    router — goes through here."""
    have = len(chain)
    if num_blocks <= have:
        return 0
    width = 4 * block_size
    buf = _pack(token_ids[have * block_size : num_blocks * block_size])
    prev = (chain[-1] if have else _namespace_seed(namespace)) or _NO_PREV
    for start in range(0, len(buf) - width + 1, width):
        prev = _block_digest(prev, buf[start : start + width])
        chain.append(prev)
    return len(chain) - have


def prefix_block_hashes(
    token_ids: Sequence[int], block_size: int, namespace: int = 0
) -> List[bytes]:
    """Chain hash of every full block of ``token_ids`` (leaving >= 1 token
    uncached, mirroring match_prefix).  These digests are the content keys
    for cross-engine prefix sharing through the remote KV store — two
    engines hashing the same tokens under the same namespace produce the
    same keys."""
    out: List[bytes] = []
    extend_prefix_chain(
        out, token_ids, block_size, (len(token_ids) - 1) // block_size,
        namespace,
    )
    return out


class BlockPool:
    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True, run: int = 1):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the null block)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self.run = max(int(run), 1)
        # Plain free blocks, an ordered set: O(1) "is b free" and removal,
        # last in first out (``popitem``); listed descending so that a
        # fresh pool hands out 1, 2, 3, ...  (0 = null block).
        self._free: Dict[int, None] = dict.fromkeys(range(num_blocks - 1, 0, -1))
        if self.run > 1:
            # Free blocks a group of ``run`` aligned ids, and the groups
            # that are wholly free, taken from the front: the fresh ones
            # ascending, then those that frees made whole again, oldest
            # first.  The null block's group and a short last one never
            # count ``run``, so they are never whole.
            groups = -(-num_blocks // self.run)
            self._group_free = [self.run] * groups
            self._group_free[0] -= 1
            self._group_free[-1] -= groups * self.run - num_blocks
            self._whole: "OrderedDict[int, None]" = OrderedDict.fromkeys(
                g for g in range(groups) if self._group_free[g] == self.run)
        self._ref_counts: Dict[int, int] = {}
        # Prefix cache: chain hash -> block id; and reverse map.
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_to_hash: Dict[int, bytes] = {}
        # Freed blocks whose content is still valid, LRU-ordered.
        self._cached_free: "OrderedDict[int, None]" = OrderedDict()
        # Metrics (token-granularity, like vLLM's hit-rate gauge).
        self.query_tokens = 0
        self.hit_tokens = 0
        # Blocks whose digest was computed through this pool (extend_chain).
        self.chain_blocks_hashed = 0
        # Called with the digest of a block whose content leaves the prefix
        # cache: what else is keyed by that digest (a state snapshot,
        # kv/state_pool.py) dies with it.
        self.on_evict = None

    # -- capacity ----------------------------------------------------------

    @property
    def num_free_blocks(self) -> int:
        return len(self._free) + len(self._cached_free)

    @property
    def usage(self) -> float:
        """Fraction of non-null blocks currently referenced by sequences."""
        total = self.num_blocks - 1
        return (total - self.num_free_blocks) / total if total else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        if not self.query_tokens:
            return 0.0
        return self.hit_tokens / self.query_tokens

    @property
    def num_cached_blocks(self) -> int:
        """Blocks whose content is reusable through the prefix cache
        (referenced or cached-free).  Exported as the
        ``tpu:prefix_cache_blocks`` gauge — the router's popularity view
        reconciles its owner map against this truth: a collapse to ~0
        means the engine restarted (or flushed) and every prefix the
        router believes resident there is gone."""
        return len(self._block_to_hash)

    # -- allocation --------------------------------------------------------

    def can_allocate(self, n: int) -> bool:
        return self.num_free_blocks >= n

    def allocate(self, n: int, after: Optional[int] = None) -> List[int]:
        """Allocate n blocks, evicting LRU cached-free blocks as needed.
        ``after``: the taker's last block, whose run the new ones continue
        where they can (module docstring)."""
        if not self.can_allocate(n):
            raise RuntimeError(
                f"KV pool exhausted: need {n} blocks, have {self.num_free_blocks}"
            )
        out: List[int] = []
        for _ in range(n):
            if self._free:
                block = self._take_free(after)
            else:
                block, _ = self._cached_free.popitem(last=False)  # LRU evict
                self._evict_hash(block)
            self._ref_counts[block] = 1
            out.append(block)
            after = block
        return out

    def _take_free(self, after: Optional[int]) -> int:
        """One plain free block: the one after ``after``, else the head of a
        wholly free group, else the last freed."""
        if self.run == 1:
            return self._free.popitem()[0]
        if after is not None and after + 1 in self._free:
            block = after + 1
            del self._free[block]
        elif self._whole:
            block = next(iter(self._whole)) * self.run
            del self._free[block]
        else:
            block = self._free.popitem()[0]
        group = block // self.run
        if self._group_free[group] == self.run:
            del self._whole[group]
        self._group_free[group] -= 1
        return block

    def free(self, blocks: Sequence[int]) -> None:
        for block in blocks:
            if block == 0:
                continue
            refs = self._ref_counts.get(block, 0) - 1
            if refs > 0:
                self._ref_counts[block] = refs
                continue
            self._ref_counts.pop(block, None)
            if block in self._block_to_hash:
                # Content still valid: keep it reclaimable via the prefix
                # cache until LRU eviction.
                self._cached_free[block] = None
                self._cached_free.move_to_end(block)
            else:
                self._free[block] = None
                if self.run > 1:
                    group = block // self.run
                    self._group_free[group] += 1
                    if self._group_free[group] == self.run:
                        self._whole[group] = None

    def _evict_hash(self, block: int) -> None:
        digest = self._block_to_hash.pop(block, None)
        if digest is not None and self._hash_to_block.get(digest) == block:
            del self._hash_to_block[digest]
            if self.on_evict is not None:
                self.on_evict(digest)

    # -- prefix caching ----------------------------------------------------

    def extend_chain(
        self,
        chain: Optional[List[bytes]],
        token_ids: Sequence[int],
        num_blocks: int,
        namespace: int = 0,
    ) -> List[bytes]:
        """``extend_prefix_chain`` at this pool's block size (a caller
        without a memo gets a chain of the call's own), counted: the pool
        belongs to the step thread, so what is hashed here is hashed there,
        while the device may be waiting for the plan."""
        if chain is None:
            chain = []
        self.chain_blocks_hashed += extend_prefix_chain(
            chain, token_ids, self.block_size, num_blocks, namespace
        )
        return chain

    def match_prefix(
        self,
        token_ids: Sequence[int],
        namespace: int = 0,
        chain: Optional[List[bytes]] = None,
    ) -> Tuple[List[int], int]:
        """Longest cached full-block prefix of token_ids.

        Returns (block_ids, num_cached_tokens); increments the matched
        blocks' refcounts (caller owns them until free()).  At least one
        token is always left uncached so prefill has work to do.

        ``chain`` is the sequence's memo of digests (``Sequence.
        prefix_chain``): what it holds is looked up and not hashed again,
        what it lacks is hashed here and kept in it.
        """
        self.query_tokens += len(token_ids)
        if not self.enable_prefix_caching:
            return [], 0
        bs = self.block_size
        # leave >=1 token for prefill
        usable_blocks = max(len(token_ids) - 1, 0) // bs
        chain = self.extend_chain(chain, token_ids, usable_blocks, namespace)
        blocks: List[int] = []
        for digest in chain[:usable_blocks]:
            block = self._hash_to_block.get(digest)
            if block is None:
                break
            blocks.append(block)
        for block in blocks:
            if block in self._cached_free:
                del self._cached_free[block]
                self._ref_counts[block] = 1
            else:
                self._ref_counts[block] = self._ref_counts.get(block, 0) + 1
        cached = len(blocks) * bs
        self.hit_tokens += cached
        return blocks, cached

    def count_cached_prefix(self, digests: Sequence[bytes]) -> int:
        """How many LEADING chain digests the cache currently holds,
        WITHOUT claiming them (no refcount change) — the admission-time
        prefetch planner uses this to size the remote miss tail."""
        if not self.enable_prefix_caching:
            return 0
        n = 0
        for digest in digests:
            if digest not in self._hash_to_block:
                break
            n += 1
        return n

    def has_digest(self, digest: bytes) -> bool:
        return digest in self._hash_to_block

    def adopt_prefix_block(self, digest: bytes, block: int) -> bool:
        """Bind an imported (remote-prefetched) block's content to its
        chain digest so match_prefix can serve it.  The caller owns the
        block (allocated, refcount 1) and frees it right after adoption,
        parking it in the reclaimable cached-free tier.  False when the
        digest is already mapped (a concurrent local prefill won the
        race): the caller's block frees as plain storage."""
        if not self.enable_prefix_caching or digest in self._hash_to_block:
            return False
        self._evict_hash(block)  # block may have held older content
        self._hash_to_block[digest] = block
        self._block_to_hash[block] = digest
        return True

    def register_prefix(
        self,
        token_ids: Sequence[int],
        block_table: Sequence[int],
        namespace: int = 0,
        chain: Optional[List[bytes]] = None,
    ) -> None:
        """Record hash chain for every *full* block of this sequence so later
        requests with the same prefix hit the cache.  With the sequence's
        ``chain`` only the blocks that its generated tokens completed are
        hashed."""
        if not self.enable_prefix_caching:
            return
        num_blocks = len(token_ids) // self.block_size
        chain = self.extend_chain(chain, token_ids, num_blocks, namespace)
        for digest, block in zip(chain[:num_blocks], block_table):
            if digest not in self._hash_to_block:
                self._evict_hash(block)  # block may have held older content
                self._hash_to_block[digest] = block
                self._block_to_hash[block] = digest
